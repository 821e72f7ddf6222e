#!/usr/bin/env python3
"""The readings the ``afmoe`` cell's ``logits_check.rel_tol`` is set from,
and what separates a fault from rounding (``tools/hybrid_probe.py`` and
``tools/logits_probe.py`` are the same idea for the families before).

    chiprun -- python benchmarks/tools/afmoe_probe.py --workload <cell> \
        --seeds 1,2 --do memory,system,rows,lower,mistakes \
        [--decode-steps 511] [--prompt-lens 6400,300] [--out probe.json] \
        [--dtype float32 --experts 8 --blocks 2000 --slots 4 --gmm-tile 512,512]

* ``memory``: the device's memory after the engine is up and after the
  warm-up (bytes in use, peak, the largest free block): what is left for
  the reference beside the engine.
* ``system``: the runner's own ``check_logits`` per seed (chunked prefill +
  decode steps through the engine's block ranges and rings with its
  kernels, against the reference). With ``--dtype float32`` the same at
  matmul precision "highest" (and a smaller pool beside float32 weights:
  ``--blocks``, ``--slots``): a bug shows there (1e-6 is rounding),
  rounding does not.
* ``rows``: the same ticks as ``check_logits`` builds (its tokens, its
  tables, its chunks of prompt rows), each decoded row in a tick of its own
  that also returns the rows every expert got (``forward_paged(with_stats=)``
  counts a tick's real rows: with one real row that IS the row's top-k set
  in every expert layer), against the reference's logits AND the experts the
  reference's router chose (``forward_logits(routes=)``): every compared
  row's ||system - reference||^2 and ||reference||^2, and every (row, expert
  layer) whose two sets differ, with the experts on either side and which of
  them are held here. The check's own number is sqrt(sum / sum) over a
  prompt's rows, the worst prompt: ``rows`` prints it for every shorter
  ``decode_steps`` too (a prefix of the rows).
* ``lower``: the reference in float8_e4m3, the nearest precision below the
  configuration's, against itself in float32, on the check's tokens and
  positions, by the check's own formula over the check's own rows, in four
  forms (``LOWER``): each layer's weights and its residual stream alone
  (PR 27's and PR 31's form: two tensors a layer, where the system rounds
  every tensor to bfloat16); then what a float8 deployment feeds its
  matrix units as well: every linear layer's input; the router's; the
  attention products' queries, keys and values (never the softmax's
  probabilities: they underflow e4m3 without a scale).
* ``mistakes``: the reference with one mistake made on purpose, on the
  check's tokens, against itself (and, with ``rows``, against the SYSTEM's
  logits: what ``correct`` would read had the program made it): no
  attention gate; rotary on the full layer; the window left off; top-3 for
  top-4; no ``route_scale``; the shared expert dropped; the embedding
  multiplier dropped; the post-norms dropped; experts 32-63 for 0-31.

Everything per row goes to ``chiprun_out/<--out>``; the log has one line a
reading and a table of the readings at every ``decode_steps`` up to the one
run.

``--rehearse 1``: the cell's rehearsal size, a dry run on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

#: mistakes in the reference's own source: name -> (old text, new text)
MISTAKES = {
    "no-attention-gate": ('    return (o * jax.nn.sigmoid(u @ lp["wg"])) '
                          '@ lp["wo"]', '    return o @ lp["wo"]'),
    "rotary-on-the-full-layer": ('    if kind == "sliding":\n        q, k = ',
                                 '    if True:\n        q, k = '),
    "window-left-off": (
        '                   arch["window"] if kind == "sliding" else 0)',
        '                   0)'),
    "top-3-for-top-4": ('arch["top_k"])', 'arch["top_k"] - 1)'),
    "no-route-scale": ('    w = w * arch["route_scale"]\n', ''),
    "shared-expert-dropped": (
        '    y = y + _mlp(u, lp["sw_gate"], lp["sw_up"], lp["sw_down"])\n',
        ''),
    "embedding-multiplier-dropped": ('.astype(jnp.float32) * '
                                     'arch["emb_mult"]',
                                     '.astype(jnp.float32)'),
    "post-norms-dropped": None,          # two places: see ``mistaken``
    "experts-32-63-for-0-31": None,      # the arch's ``first_expert``
}


def mistaken(source: str, name: str) -> str:
    if name == "post-norms-dropped":
        for old, new in (
                ('    x = x + _rms_norm(a, lp["ln1_post"]["scale"], eps)',
                 '    x = x + a'),
                ('    x = x + _rms_norm(f, lp["ln2_post"]["scale"], eps)',
                 '    x = x + f')):
            assert source.count(old) == 1, (name, old)
            source = source.replace(old, new)
        return source
    old, new = MISTAKES[name]
    assert source.count(old) == 1, (name, old)
    return source.replace(old, new)


#: the nearest precision below the configuration's bfloat16 (a round trip
#: through bfloat16 itself XLA elides: ``xla_allow_excess_precision``)
FLOAT8 = ".astype(jnp.float8_e4m3fn).astype(jnp.float32)"

#: a float8 deployment's recipe, by what it feeds a float8 matrix unit
#: beside the weights: the input of every LINEAR layer (projections, dense
#: and expert MLPs, the head and its matrix) through ``_q``; the router's
#: input; the attention products' queries, keys and values. The softmax's
#: probabilities are left out of every form: 1/4096 lies under e4m3's
#: smallest subnormal (2**-9), so without a scale a window's probabilities
#: round to zero (read once, call 12: 0.75-0.77, an underflow and no rounding)
_FEEDS = {
    "linears": (
        ('    q = _rms_norm((u @ lp["wq"])',
         '    q = _rms_norm((_q(u) @ lp["wq"])'),
        ('    k = _rms_norm((u @ lp["wk"])',
         '    k = _rms_norm((_q(u) @ lp["wk"])'),
        ('    v = (u @ lp["wv"])', '    v = (_q(u) @ lp["wv"])'),
        ('    return (o * jax.nn.sigmoid(u @ lp["wg"])) @ lp["wo"]',
         '    return _q(o * jax.nn.sigmoid(_q(u) @ lp["wg"])) @ lp["wo"]'),
        ('    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down',
         '    return _q(jax.nn.silu(_q(x) @ w_gate) * (_q(x) @ w_up)) '
         '@ w_down'),
        ('out, x @ w.astype(jnp.float32), lo, axis=2)',
         'out, _q(x) @ _q(w), lo, axis=2)')),
    "router": (('    scores = jax.nn.sigmoid(u @ lp["gate_w"])',
                '    scores = jax.nn.sigmoid(_q(u) @ lp["gate_w"])'),),
    "attention": (('jnp.einsum("qkrd,skd->krqs", qb, k)',
                   'jnp.einsum("qkrd,skd->krqs", _q(qb), _q(k))'),
                  ('jnp.einsum("krqs,skd->qkrd", p, v)',
                   'jnp.einsum("krqs,skd->qkrd", p, _q(v))')),
}


def lowered(source: str, cast: str, feeds=()) -> str:
    """The reference's source with every weight a layer reads (an expert's
    matrices as they are read) and the residual stream it hands on put
    through ``cast`` (the form PRs 27 and 31 read their cells' lower limit
    from: two tensors a layer); with ``feeds`` (keys of ``_FEEDS``) the
    matmuls' other operands as well: COMPUTED in that precision, as the
    system computes in bfloat16 (sums stay float32)."""
    edits = sum((_FEEDS[k] for k in feeds), ())
    if edits:
        source += "\n\ndef _q(a):\n    return a" + cast + "\n"
    for old, new in edits + (
            ("(1, 1) + stack[name].shape[2:])[0, 0].astype(jnp.float32)",
             "(1, 1) + stack[name].shape[2:])[0, 0]" + cast),
            ("    lp = _f32(lp)\n",
             "    lp = jax.tree.map(lambda a: a" + cast + ", lp)\n"),
            ('    x = x + _rms_norm(f, lp["ln2_post"]["scale"], eps)',
             '    x = (x + _rms_norm(f, lp["ln2_post"]["scale"], eps))'
             + cast)):
        assert source.count(old) == 1, old
        source = source.replace(old, new)
    return source


#: the forms of "the reference in float8_e4m3" the probe reads
LOWER = {
    "reference_in_float8_e4m3": (),
    "reference_linears_in_float8_e4m3": ("linears",),
    "reference_linears_and_router_in_float8_e4m3": ("linears", "router"),
    "reference_computed_in_float8_e4m3": ("linears", "router", "attention"),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--do", default="system")
    ap.add_argument("--dtype", default="")
    ap.add_argument("--prompt-lens", default="")
    ap.add_argument("--decode-steps", type=int, default=0)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--out", default="afmoe_probe.json")
    ap.add_argument("--mistakes", default="",
                    help="these of the nine alone (names, comma-separated)")
    ap.add_argument("--blocks", type=int, default=0,
                    help="a smaller pool (float32 weights beside it)")
    ap.add_argument("--slots", type=int, default=0)
    ap.add_argument("--experts", type=int, default=0,
                    help="hold fewer experts (float32 weights fit then)")
    ap.add_argument("--gmm-tile", default="",
                    help="tk,tn of the grouped matmul's weight tile (float32 "
                         "at precision \"highest\" needs a smaller one: the "
                         "tiling alone changes, not the arithmetic)")
    args = ap.parse_args()

    import contextlib
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import harness, manifest, model_config, weights
    from benchmarks.runners import serve

    cell = manifest.load_cell(args.workload)
    reference = manifest.load_plugin("reference", cell.config["reference"])
    if args.dtype:
        cell.config["compute_dtype"] = args.dtype
    if args.blocks:
        cell.deploy["engine"]["n_blocks"] = args.blocks
    if args.slots:
        cell.deploy["engine"]["state_slots"] = args.slots
    if args.experts:
        cell.config["num_experts"] = args.experts
    if args.gmm_tile:
        from deepspeed_tpu.moe import layer as moe_layer

        tile = tuple(int(n) for n in args.gmm_tile.split(","))
        moe_layer._whole_k_tile = lambda K, N, itemsize=2: tile
    hf = model_config.hf_kwargs(cell.config, "serve")
    if args.rehearse:
        hf.update(cell.config["rehearse"])
    arch = reference.arch_from_config(cell.config, hf)
    held = range(arch["first_expert"],
                 arch["first_expert"] + hf["num_experts"])
    seeds = [int(s) for s in args.seeds.split(",")]
    todo = args.do.split(",")
    spec = dict(cell.deploy.get("rehearse", {}).get("logits_check", {})
                if args.rehearse else cell.deploy["logits_check"])
    if args.prompt_lens:
        spec["prompt_lens"] = [int(n) for n in args.prompt_lens.split(",")]
    if args.decode_steps:
        spec["decode_steps"] = args.decode_steps
    n_dec = int(spec["decode_steps"])
    highest = cell.config["compute_dtype"] == "float32"
    out = {"cell": cell.name, "dtype": cell.config["compute_dtype"],
           "prompt_lens": spec["prompt_lens"], "decode_steps": n_dec,
           "readings": {}, "rows": {}}

    def say(key, seed, value):
        out["readings"].setdefault(key, {})[str(seed)] = value
        harness.log(f"probe: {key} seed {seed}: {value}")

    def memory():
        stats = jax.devices()[0].memory_stats() or {}
        return {k: stats.get(k) for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_limit",
            "largest_free_block_bytes", "bytes_reserved")}

    def session_for(seed):
        s = serve.Session(cell, types.SimpleNamespace(
            seed=seed, rehearse=bool(args.rehearse)))
        s.deploy["logits_check"] = spec
        return s

    def closed(s):      # the next session's weights and pools need the room
        s.engine.params = s.engine.pool = None
        s.fe.close()
        gc.collect()

    def precision():
        return jax.default_matmul_precision("highest") if highest \
            else contextlib.nullcontext()

    def check(seed, warm=False):
        s = session_for(seed)
        try:
            if warm:
                say("memory_engine_up", seed, memory())
                s.warm()
                say("memory_after_warmup", seed, memory())
            with precision():
                return s.check_logits()
        finally:
            closed(s)
            del s

    def the_checks_tokens(seed, vocab):
        """(tokens, compared positions) as ``check_logits`` draws them."""
        rng = np.random.default_rng([seed, 7])
        return [(rng.integers(0, vocab, n + n_dec).astype(np.int32),
                 list(range(n - 1, n + n_dec))) for n in spec["prompt_lens"]]

    def system_rows(s, seed):
        """``check_logits``'s ticks, but for the decoded rows: one real row
        a tick of the small bucket, with the tick's rows per expert. Returns
        per prompt (logits [rows, V] float32 on the host, the top-k set of
        every decoded row in every expert layer [steps, layers, k])."""
        from deepspeed_tpu.models import paged as PG

        eng, cfg = s.engine, s.cfg
        if eng._use_kernel:
            from deepspeed_tpu.ops.pallas.paged_attention import \
                paged_attention as attn
        else:
            attn = PG.paged_attention_reference
        Tn, mb, bs = eng.token_budget, eng.max_blocks_per_seq, eng.block_size
        Td = min(Tn, 256)
        seqs = []
        for toks, at in the_checks_tokens(seed, cfg.vocab_size):
            blocks = eng.allocator.allocate(len(toks) // bs + 1)
            table = np.zeros((mb,), np.int32)
            table[:len(blocks)] = blocks
            seqs.append({"toks": toks, "n": at[0] + 1, "blocks": blocks,
                         "table": table, "logits": [], "sets": []})

        def run(stats):
            return jax.jit(
                lambda params, pool, tokens, positions, tables:
                PG.forward_paged(params, tokens, positions, tables, pool,
                                 cfg, attention_fn=attn, with_stats=stats),
                donate_argnums=(1,))

        chunk, one = run(False), run(True)

        def tick(fn, rows, T):
            tokens = np.zeros((T,), np.int32)
            positions = np.zeros((T,), np.int32)
            tables = np.zeros((T, mb), np.int32)
            for r, (q, p) in enumerate(rows):
                tokens[r], positions[r], tables[r] = q["toks"][p], p, \
                    q["table"]
            res = fn(eng.params, eng.pool, jnp.asarray(tokens),
                     jnp.asarray(positions), jnp.asarray(tables))
            eng.pool = res[1]
            return res[0], res[2:]

        prefill = [(q, p) for q in seqs for p in range(q["n"])]
        for lo in range(0, len(prefill), Tn):
            rows = prefill[lo:lo + Tn]
            logits, _ = tick(chunk, rows, Tn)
            for r, (q, p) in enumerate(rows):
                if p == q["n"] - 1:
                    q["logits"].append(logits[r])
        for step in range(n_dec):
            for q in seqs:
                logits, (stats,) = tick(one, [(q, q["n"] + step)], Td)
                q["logits"].append(logits[0])
                q["sets"].append(stats["expert_rows"])
        res = []
        for q in seqs:
            counts = np.asarray(jnp.stack(q["sets"]))     # [steps, L, E]
            assert (counts.sum(-1) == arch["top_k"]).all() \
                and counts.max() == 1, "one real row a tick"
            sets = np.argsort(-counts, axis=-1, kind="stable")[
                ..., :arch["top_k"]]
            res.append((np.asarray(jnp.stack(q["logits"]), np.float32),
                        np.sort(sets, axis=-1)))
            eng.allocator.free(q["blocks"])
        return res

    def squares(got, want):
        """Per row: (||got - want||^2, ||want||^2)."""
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        return ((got - want) ** 2).sum(-1), (want ** 2).sum(-1)

    def reading(pairs, rows=None):
        """The check's number over the first ``rows`` rows of each prompt:
        the worst prompt's sqrt(sum / sum)."""
        return max(float(np.sqrt(num[:rows].sum() / den[:rows].sum()))
                   for num, den in pairs)

    def differing(a, b):
        """(row, layer, experts only in a, experts only in b) wherever two
        [rows, layers, k] choices differ as sets."""
        a, b = np.sort(a, -1), np.sort(b, -1)
        found = []
        for r, l in zip(*np.nonzero((a != b).any(-1))):
            sa, sb = set(a[r, l].tolist()), set(b[r, l].tolist())
            found.append((int(r), int(l), sorted(sa - sb), sorted(sb - sa)))
        return found

    def straddles(only_a, only_b):
        """A swap counts here where the experts exchanged are not all held
        and not all absent."""
        inside = [e in held for e in only_a + only_b]
        return any(inside) and not all(inside)

    if "memory" in todo:
        say("system", seeds[0], check(seeds[0], warm=True))
        say("memory_after_check", seeds[0], memory())
    if "system" in todo:
        for seed in seeds["memory" in todo:]:
            say("system", seed, check(seed))

    with open(reference.__file__) as f:
        source = f.read()
    variants = {}
    if "lower" in todo:
        for name, feeds in LOWER.items():
            variants[name] = (lowered(source, FLOAT8, feeds), arch)
    if "mistakes" in todo:
        for name in args.mistakes.split(",") if args.mistakes else MISTAKES:
            # the weights held stand for the next share's experts
            variants[name] = (None, {**arch,
                                     "first_expert": hf["num_experts"]}) \
                if name == "experts-32-63-for-0-31" \
                else (mistaken(source, name), arch)
    modules = {}
    for name, (text, a) in variants.items():
        mod = reference
        if text is not None:
            mod = types.ModuleType("variant_" + name.replace("-", "_"))
            exec(compile(text, reference.__file__, "exec"), mod.__dict__)
        modules[name] = mod, a

    for seed in seeds if variants or "rows" in todo else ():
        cfg = model_config.build(cell.config, "serve",
                                 rehearse=bool(args.rehearse))
        per_seed = out["rows"][str(seed)] = {}
        system = None
        if "rows" in todo:
            s = session_for(seed)
            params = s.engine.params
            try:
                with precision():
                    system = system_rows(s, seed)
            finally:
                closed(s)
                del s
        else:
            params = weights.init_on_device(cfg, seed)
        tokens = the_checks_tokens(seed, cfg.vocab_size)
        wanted = []
        for toks, at in tokens:
            routes = []
            want = np.asarray(reference.forward_logits(
                params, toks[None], arch, at=at, routes=routes)[0])
            wanted.append((want, np.stack([np.asarray(r) for r in routes],
                                          axis=1)))      # [rows, layers, k]
        harness.log(f"probe: reference of seed {seed} done")
        if system is not None:
            pairs, n_swaps = [], 0
            for i, ((got, sets), (want, chosen)) in enumerate(
                    zip(system, wanted)):
                num, den = squares(got, want)
                pairs.append((num, den))
                # the first compared row is a prompt row: no set of its own
                found = [(r + 1, l, a, b)
                         for r, l, a, b in differing(sets, chosen[1:])]
                across = sorted({r for r, _, a, b in found
                                 if straddles(a, b)})
                other = sorted({r for r, _, _, _ in found} - set(across))
                rel = np.sqrt(num / den)
                clean = np.setdiff1d(np.arange(1, len(rel)), across + other)
                per_seed[f"system.{i}"] = {
                    "num": num.tolist(), "den": den.tolist(),
                    "differing": found}
                n_swaps += len(across)

                def mean(rows):
                    return round(float(np.mean(rel[rows])), 4) \
                        if len(rows) else None

                harness.log(
                    f"probe: rows seed {seed} prompt "
                    f"{spec['prompt_lens'][i]}: {len(rel)} rows; "
                    f"{len(across)} with a swap across held/absent (mean row "
                    f"diff {mean(across)}), {len(other)} with another swap "
                    f"({mean(other)}), {len(clean)} with none ({mean(clean)},"
                    f" largest {mean([int(np.argmax(rel * np.isin(np.arange(len(rel)), clean)))])})")
                for r in np.argsort(-rel)[:6]:
                    harness.log(
                        f"probe:   row {int(r)} diff {rel[r]:.4f} "
                        + "; ".join(f"layer {l}: system {a} reference {b}"
                                    for q, l, a, b in found if q == r))
            say("system_by_rows", seed, round(reading(pairs), 5))
            say("rows_with_a_swap_across", seed, n_swaps)
        for name, (mod, a) in modules.items():
            pairs, against, n_diff = [], [], 0
            for i, ((toks, at), (want, chosen)) in enumerate(
                    zip(tokens, wanted)):
                routes = []
                got = np.asarray(mod.forward_logits(
                    params, toks[None], a, at=at, routes=routes)[0])
                pairs.append(squares(got, want))
                per_seed[f"{name}.{i}"] = {
                    "num": pairs[-1][0].tolist(),
                    "den": pairs[-1][1].tolist()}
                theirs = np.stack([np.asarray(r) for r in routes], 1)
                if theirs.shape == chosen.shape:
                    n_diff += len({r for r, _, _, _ in
                                   differing(theirs, chosen)})
                if system is not None:
                    # what the check would read had the PROGRAM been the
                    # variant: the system against the variant as reference
                    against.append(squares(system[i][0], got))
                    per_seed[f"system_vs_{name}.{i}"] = {
                        "num": against[-1][0].tolist(),
                        "den": against[-1][1].tolist()}
            say(name, seed, round(reading(pairs), 5))
            say(name + ".rows_routed_otherwise", seed, n_diff)
            if against:
                say("system_vs_" + name, seed, round(reading(against), 5))
        del params, wanted
        gc.collect()

    # every reading again at each shorter ``decode_steps`` (a prefix of rows)
    steps = sorted({n for n in (8, 16, 32, 64, 128, 256, 512, 1024)
                    if n < n_dec} | {n_dec})
    table = out["by_decode_steps"] = {}
    names = sorted({k.rsplit(".", 1)[0] for v in out["rows"].values()
                    for k in v})
    for name in names:
        for n in steps:
            table.setdefault(name, {})[str(n)] = [
                round(reading([(np.asarray(v[f"{name}.{i}"]["num"]),
                                np.asarray(v[f"{name}.{i}"]["den"]))
                               for i in range(len(spec["prompt_lens"]))],
                              rows=n + 1), 5)
                for v in out["rows"].values()]
            harness.log(f"probe: {name} at decode_steps {n}: "
                        f"{table[name][str(n)]}")

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", args.out), "w") as f:
        json.dump(out, f)
    print(json.dumps({k: v for k, v in out.items() if k != "rows"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
