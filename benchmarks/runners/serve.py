"""Runner ``serve``: one ``FastGenEngine`` behind ``ServingFrontend``,
driven by one thread the way the frontend is meant to be driven
(``submit`` between ticks, ``run_tick``, read what became visible).

Timing method: the client's clock is ``time.perf_counter`` of this one
process. A token is *visible* at the end of the tick that sampled it
(``run_tick`` returns after the sampled tokens were read back from the
device, so every tick is fenced). An open-loop request is timed from the
instant it was due, not from when the loop got round to submitting it;
how late the loop was is reported beside it (``gen_late``). Traffic runs
through a pre-roll before the window opens so the window starts on a
steady state; requests in flight at the end are drained after it,
outside the timing, so the block accounting can be checked.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks import device as devmod
from benchmarks import harness, model_config, weights
from benchmarks.harness import check, log
from benchmarks.manifest import Cell, load_plugin


class Session:
    """One engine + frontend and the client's log of what it saw."""

    def __init__(self, cell: Cell, args):
        import jax

        from deepspeed_tpu import telemetry
        from deepspeed_tpu.inference.fastgen import FastGenEngine
        from deepspeed_tpu.serving import ServingFrontend

        self.cell, self.args = cell, args
        deploy = dict(cell.deploy)
        if args.rehearse:
            deploy.update(deploy.get("rehearse", {}))
        self.deploy = deploy
        self.cfg = model_config.build(cell.config, "serve",
                                      rehearse=args.rehearse)
        log("serve: model config built")
        params = weights.init_on_device(self.cfg, args.seed)
        jax.block_until_ready(params)
        log(f"serve: weights on the device, depth {self.cfg.num_layers}")
        self.engine = FastGenEngine(
            self.cfg, params, use_pallas_kernel=True, seed=args.seed,
            **deploy["engine"])
        self.fe = ServingFrontend(self.engine,
                                  config=deploy.get("serving") or None)
        log("serve: engine and frontend up")
        self.free_at_start = self.engine.allocator.free_blocks
        self._fails = telemetry.counter("serving_tick_failures_total")
        self._prefill = telemetry.counter("fastgen_prefill_tokens_total")
        self._gen = telemetry.counter("fastgen_generated_tokens_total")
        self.fails_before = self._fails.total()
        self._uid_base = 0
        self.mosaic = False

    # -------------------------------------------------------------- #
    def warm(self) -> None:
        """One request at a time through every (tick bucket, table tier)
        the cell's traffic can reach: the lengths are the cell file's."""
        w = self.deploy["warmup"]
        for i, n in enumerate(w["prompt_lens"]):
            uid = -1 - i
            prompt = np.random.default_rng([self.args.seed, 9, i]).integers(
                0, self.cfg.vocab_size, n).tolist()
            self.fe.submit(uid, prompt, max_new_tokens=w["max_new"])
            while self.fe.active_count():
                self.fe.run_tick()
            res = self.fe.result(uid)
            check(res.state == "completed" and len(res.tokens) == w["max_new"],
                  f"warm-up request of {n} tokens ended {res.state} with "
                  f"{len(res.tokens)} tokens")
            self.fe.drop_result(uid)
        log("serve: warm-up done")

    # -------------------------------------------------------------- #
    def check_logits(self) -> float:
        """Chunked prefill, then decode steps, through the engine's own
        paged pool with the cell's attention function, against the
        configuration's plain reference (full forward at the same
        positions): logits, not tokens. Returns the worst
        ||system - reference|| / ||reference|| over the sampled prompts,
        each over the logits of its last prompt position and the decoded
        positions; the tolerance is the cell file's
        ``logits_check.rel_tol``, with its reason beside it."""
        import jax
        import jax.numpy as jnp

        from deepspeed_tpu.models import paged as PG

        reference = load_plugin("reference", self.cell.config["reference"])

        eng, cfg, spec = self.engine, self.cfg, self.deploy["logits_check"]
        if eng._use_kernel:
            from deepspeed_tpu.ops.pallas.paged_attention import \
                paged_attention as attn
        else:
            attn = PG.paged_attention_reference
        Tn, mb, bs = eng.token_budget, eng.max_blocks_per_seq, eng.block_size
        n_dec = int(spec["decode_steps"])
        rng = np.random.default_rng([self.args.seed, 7])
        seqs = []
        for n in spec["prompt_lens"]:
            toks = rng.integers(0, cfg.vocab_size, n + n_dec).astype(np.int32)
            blocks = eng.allocator.allocate((n + n_dec) // bs + 1)
            table = np.zeros((mb,), np.int32)
            table[:len(blocks)] = blocks
            seqs.append({"toks": toks, "n": n, "blocks": blocks,
                         "table": table, "logits": {}})

        def fwd(params, pool, tokens, positions, tables):
            return PG.forward_paged(params, tokens, positions, tables, pool,
                                    cfg, attention_fn=attn)

        fwd = jax.jit(fwd, donate_argnums=(1,))   # the pool fits once

        def tick(rows):
            """rows: (seq, position) pairs, at most Tn; pads carry an
            all-zero table and land in the trash block."""
            tokens = np.zeros((Tn,), np.int32)
            positions = np.zeros((Tn,), np.int32)
            tables = np.zeros((Tn, mb), np.int32)
            for r, (s, p) in enumerate(rows):
                tokens[r], positions[r], tables[r] = s["toks"][p], p, s["table"]
            logits, eng.pool = fwd(eng.params, eng.pool, jnp.asarray(tokens),
                                   jnp.asarray(positions), jnp.asarray(tables))
            for r, (s, p) in enumerate(rows):
                if p >= s["n"] - 1:
                    s["logits"][p] = logits[r]

        # the same paged forward with the same attention function as the
        # engine's tick: does it reach Mosaic?
        zeros = jnp.zeros((Tn,), jnp.int32)
        self.mosaic = "tpu_custom_call" in fwd.lower(
            eng.params, eng.pool, zeros, zeros,
            jnp.zeros((Tn, mb), jnp.int32)).as_text() and eng._use_kernel
        prefill = [(s, p) for s in seqs for p in range(s["n"])]
        for lo in range(0, len(prefill), Tn):
            tick(prefill[lo:lo + Tn])
        for step in range(n_dec):
            tick([(s, s["n"] + step) for s in seqs])

        hf = model_config.hf_kwargs(self.cell.config, "serve")
        if self.args.rehearse:
            hf.update(self.cell.config["rehearse"])
        arch = reference.arch_from_config(self.cell.config, hf)
        tol = float(spec["rel_tol"])
        worst = 0.0
        for s in seqs:
            at = list(range(s["n"] - 1, s["n"] + n_dec))
            got = jnp.stack([s["logits"][p] for p in at]).astype(jnp.float32)
            want = reference.forward_logits(
                eng.params, s["toks"][None, :], arch)[0, jnp.asarray(at)]
            rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
            log(f"serve: paged logits vs reference, prompt of {s['n']}: "
                f"rel. diff {rel:.3e} (tolerance {tol:.1e})")
            worst = max(worst, rel)
            eng.allocator.free(s["blocks"])
        return worst

    # -------------------------------------------------------------- #
    def drive(self, source, preroll_s: float, seconds: float,
              tail_s: float = 0.0, profiler: Optional[harness.Profiler] = None
              ) -> Dict[str, Any]:
        """Run traffic from ``-preroll_s`` to ``seconds + tail_s`` on a
        clock whose zero is the start of the window; with a profiler, the
        tail is the traced stretch. Returns the client's log."""
        from jax.profiler import TraceAnnotation

        from deepspeed_tpu.serving import Admitted

        fe, eng = self.fe, self.engine
        origin = time.perf_counter() + preroll_s
        clock = lambda: time.perf_counter() - origin   # noqa: E731
        reqs: Dict[int, Dict[str, Any]] = {}
        live: Dict[int, Dict[str, Any]] = {}
        ticks: List[tuple] = []
        marks: Dict[str, Any] = {}
        window_span = None
        base = self._uid_base
        end = seconds + tail_s

        def snapshot(tag: str) -> None:
            marks[tag] = {"telemetry": harness.telemetry_snapshot(),
                          "active": fe.active_count(),
                          "ticks": len(ticks), "t": clock()}

        def harvest(t: float) -> None:
            active = set(fe.active_uids())
            for uid, rec in list(live.items()):
                if uid in active:
                    n = len(eng.seqs[uid].generated)
                else:
                    res = fe.result(uid)
                    n = len(res.tokens)
                    rec["state"], rec["done_t"] = res.state, t
                    rec["in_range"] = all(
                        0 <= x < self.cfg.vocab_size for x in res.tokens)
                    fe.drop_result(uid)
                    del live[uid]
                    source.on_complete(rec["request"], t)
                rec["stamps"].extend([t] * (n - len(rec["stamps"])))

        def submit(due: List[Any]) -> None:
            if not due:
                return
            with TraceAnnotation("bench.submit"):
                for r in due:
                    uid = base + r.uid
                    t_sub = clock()
                    res = fe.submit(uid, r.prompt, max_new_tokens=r.max_new)
                    rec = {"request": r, "due": r.due, "submit_t": t_sub,
                           "stamps": [], "state": None, "done_t": None,
                           "admitted": isinstance(res, Admitted),
                           "degraded": getattr(res, "degraded", False),
                           "in_range": True}
                    reqs[uid] = rec
                    if rec["admitted"]:
                        live[uid] = rec
                    else:
                        rec["state"], rec["done_t"] = "refused", t_sub
                        fe.drop_result(uid)
                        source.on_complete(r, t_sub)

        while True:
            now = clock()
            if "open" not in marks and now >= 0:
                snapshot("open")
            if "close" not in marks and now >= seconds:
                snapshot("close")
                if profiler is not None:
                    profiler.start()
                    window_span = TraceAnnotation("bench.trace_window")
                    window_span.__enter__()
                    marks["trace_from_tick"] = len(ticks)
            if now >= end:
                break
            submit(source.due(now))
            if not fe.active_count():
                nxt = source.next_due()
                with TraceAnnotation("bench.sleep"):
                    time.sleep(min(0.002, max(0.0, (nxt if nxt is not None
                                                    else end) - clock())))
                continue
            p0, g0 = self._prefill.total(), self._gen.total()
            pos0 = {u: eng.seqs[u].pos for u in live if u in eng.seqs}
            t0 = clock()
            with TraceAnnotation("bench.tick"):
                fe.run_tick()
            t1 = clock()
            dp, dg = self._prefill.total() - p0, self._gen.total() - g0
            with TraceAnnotation("bench.tick.mixed" if dp else
                                 "bench.tick.decode"):
                pass
            # cache blocks of the sequences that had rows in this tick:
            # what an ideal kernel reads (each sequence's cache once)
            blocks = 0
            for u, p in pos0.items():
                seq = eng.seqs.get(u)
                pos = seq.pos if seq is not None else p + 1
                if pos > p:
                    blocks += (pos - 1) // eng.block_size + 1
            ticks.append((t0, t1, int(dp), int(dg), blocks))
            with TraceAnnotation("bench.harvest"):
                harvest(t1)

        marks["trace_to_tick"] = len(ticks)
        trace = None
        if window_span is not None:
            window_span.__exit__(None, None, None)
            trace = profiler.stop()
        # what fell due during the last tick is still sent (it counts as
        # attempted); then drain what is in flight, outside the timing
        if not source.closed:
            submit(source.due(end))
        source.stop()
        guard = time.perf_counter()
        while fe.active_count():
            check(time.perf_counter() - guard < 120, "the server never drained")
            fe.run_tick()
            harvest(clock())
        self._uid_base = base + 10_000_000
        return {"requests": reqs, "ticks": ticks, "marks": marks,
                "seconds": seconds, "closed": source.closed, "trace": trace}

    # -------------------------------------------------------------- #
    def accounting(self, client: Dict[str, Any]) -> None:
        """The smoke's checks: exact token counts, tokens in range, no
        failed tick, breaker closed, every block back."""
        for uid, rec in client["requests"].items():
            if rec["state"] == "completed" and not rec["degraded"]:
                check(len(rec["stamps"]) == rec["request"].max_new,
                      f"request {uid}: {len(rec['stamps'])} tokens, asked "
                      f"for {rec['request'].max_new}")
            check(rec["in_range"], f"request {uid}: token out of range")
            check(rec["state"] is not None, f"request {uid} never resolved")
        rose = self._fails.total() - self.fails_before
        check(rose == 0, f"serving_tick_failures_total rose by {rose}")
        check(self.fe.breaker.state == "closed",
              f"circuit breaker ended {self.fe.breaker.state}")
        check(self.engine.allocator.free_blocks == self.free_at_start,
              f"KV blocks leaked: {self.engine.allocator.free_blocks} free "
              f"of {self.free_at_start}")


def failed(rec: Dict[str, Any]) -> bool:
    """Refused, shed, expired, failed, degraded, or answered short."""
    return (not rec["admitted"] or rec["degraded"]
            or rec["state"] != "completed"
            or len(rec["stamps"]) != rec["request"].max_new)


def measured(client: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Open loop: the requests due inside the window. Closed loop: the
    requests that ended inside it."""
    s = client["seconds"]
    if client["closed"]:
        return [r for r in client["requests"].values()
                if r["done_t"] is not None and 0 <= r["done_t"] < s]
    return [r for r in client["requests"].values() if 0 <= r["due"] < s]


def run(cell: Cell, args, device: Dict[str, Any]) -> harness.RunRecord:
    session = Session(cell, args)
    deploy = session.deploy
    session.warm()
    peak_warm = devmod.memory_peak_bytes(cell.chips)
    rel = session.check_logits()
    peak_checked = devmod.memory_peak_bytes(cell.chips)
    traffic = dict(cell.traffic["params"])
    if args.rehearse:
        traffic.update(cell.traffic.get("rehearse", {}))
    preroll = float(traffic["preroll_s"])
    tail = float(deploy["trace_seconds"]) if args.trace else 0.0
    source = load_plugin("generators", cell.traffic["generator"]).build(
        traffic, args.seed, session.cfg.vocab_size, session.engine.max_len,
        start_s=-preroll, end_s=args.seconds + tail)
    compiles = harness.CompileCounter()
    setup_s = time.perf_counter() - harness.T0 + preroll
    client = session.drive(
        source, preroll, args.seconds, tail,
        harness.Profiler(cell.name) if args.trace else None)
    compiles_in_run = compiles.count

    tol = float(deploy["logits_check"]["rel_tol"])
    check(rel <= tol, f"paged logits vs reference: rel. diff {rel:.3e} > {tol}")
    session.accounting(client)
    check(args.rehearse or session.mosaic,
          "the paged forward holds no tpu_custom_call: the paged kernel did "
          "not go through Mosaic")
    check(compiles_in_run == 0,
          f"{compiles_in_run} program(s) compiled after the warm-up")
    session.fe.close()

    rows = measured(client)
    marks = client["marks"]
    telemetry = harness.Telemetry(marks["open"]["telemetry"],
                                  marks["close"]["telemetry"])
    log(f"serve: window done, {len(rows)} requests measured, "
        f"{len(client['ticks'])} ticks")
    return harness.RunRecord(
        cell=cell, seconds=args.seconds, chips=cell.chips, device=device,
        peaks=None, model=session.cfg, setup_s=setup_s, client=client,
        telemetry=telemetry, trace=client["trace"],
        extras={"logits_rel_diff": rel,
                "program_peak_bytes": devmod.program_peak_bytes(
                    peak_warm, peak_checked,
                    devmod.memory_peak_bytes(cell.chips)),
                "peak_bytes_after_warmup": peak_warm,
                "peak_bytes_after_reference": peak_checked,
                "active_at_open": marks["open"]["active"],
                "active_at_close": marks["close"]["active"],
                "compiles_after_warmup": compiles_in_run,
                # a run that sheds or degrades measured another path:
                # seen in untraced runs too (``tools/repeat.py`` keeps them)
                "kv_pool_peak": telemetry.gauge(
                    "fastgen_kv_pool_utilization_peak"),
                "degraded": sum(1 for r in rows if r["degraded"]),
                "refused": sum(1 for r in rows if not r["admitted"]),
                "engine": dict(deploy["engine"])},
        attempted=len(rows), failed=sum(1 for r in rows if failed(r)))
