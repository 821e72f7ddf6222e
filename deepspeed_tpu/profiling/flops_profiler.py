"""FLOPS profiler — exact counts from XLA cost analysis.

Parity: reference ``profiling/flops_profiler/profiler.py:30`` (``FlopsProfiler``,
``get_model_profile``). The reference monkey-patches ~50 torch functionals to
count MACs as the model runs (:880); on TPU the compiled HLO *is* the ground
truth, so the profiler asks XLA's cost analysis for flops/bytes — exact, free,
and inclusive of fusion effects the reference can't see.

What a cost-analysis compile costs is accounted where every compile of the
process is: ``xla_program_seconds_total{program=<fn>}`` and the flight
recorder's ``xla_compile`` span (``telemetry/host.py``), once an engine has
installed that account.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

PyTree = Any

def normalize_costs(raw: Any) -> Dict[str, float]:
    """Normalize ``compiled.cost_analysis()`` across jax versions: a dict,
    a [dict] list (older jax), an empty list, or None all become a plain
    dict (possibly empty). Never raises on weird shapes."""
    if isinstance(raw, (list, tuple)):
        raw = raw[0] if raw else {}
    try:
        return dict(raw or {})
    except (TypeError, ValueError):
        return {}


def cost_analysis_available(costs: Dict[str, float]) -> bool:
    """True when the normalized costs actually carry a FLOP count. Some
    jax/jaxlib builds return an empty dict or a list without 'flops' —
    reporting those as 0 FLOPs silently poisons every utilization figure
    downstream, so callers must branch on this instead."""
    return bool(costs) and "flops" in costs


def _cost_analysis(fn: Callable, *args, **kwargs) -> Dict[str, float]:
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    try:
        raw = compiled.cost_analysis()
    except (RuntimeError, NotImplementedError, TypeError):
        # some backends/builds don't implement cost analysis at all —
        # degrade to the explicit unavailable flag, same as an empty dict
        raw = None
    return normalize_costs(raw)


def profile_fn(fn: Callable, *args, **kwargs) -> Dict[str, float]:
    """→ {'flops': ..., 'bytes_accessed': ..., 'cost_analysis_unavailable':
    bool, ...} for fn(*args). When the backend's cost analysis yields no
    usable costs the numeric fields are 0 AND the flag is set — callers
    must not treat the zeros as measurements."""
    costs = _cost_analysis(fn, *args, **kwargs)
    return {
        "flops": float(costs.get("flops", 0.0)),
        "bytes_accessed": float(costs.get("bytes accessed", 0.0)),
        "transcendentals": float(costs.get("transcendentals", 0.0)),
        "cost_analysis_unavailable": not cost_analysis_available(costs),
    }


class FlopsProfiler:
    """Engine-attached profiler (reference engine hook ``engine.py:360``).

    Usage::

        prof = FlopsProfiler(engine)
        prof.start_profile()
        engine.train_batch(data)       # timed
        prof.stop_profile()
        prof.print_profile()
    """

    def __init__(self, engine=None):
        self.engine = engine
        self._t0: Optional[float] = None
        self.elapsed: float = 0.0
        self.flops: float = 0.0
        self.params: Optional[int] = None
        # set by profile_train_step when XLA's cost analysis yields no
        # usable costs on this jax/jaxlib build — flops 0.0 then means
        # "unknown", NOT "measured zero"
        self.cost_analysis_unavailable: bool = False

    # -- lifecycle (reference API names) --------------------------------- #
    def start_profile(self) -> None:
        if self.engine is not None:
            self.flops = self.profile_train_step()
            self.params = self.engine.model_spec.num_params
        self._t0 = time.perf_counter()

    def stop_profile(self) -> None:
        if self._t0 is not None:
            self.elapsed = time.perf_counter() - self._t0
            self._t0 = None

    def profile_train_step(self) -> float:
        """FLOPs of one compiled train step (fwd+bwd+update)."""
        eng = self.engine
        gas = eng.gradient_accumulation_steps()
        # reuse the live compiled step when present; else build the PLAIN
        # step. Seed the engine cache (setdefault: atomic under the GIL,
        # safe from a telemetry scrape thread; keeps the documented
        # start_profile -> train_batch flow to ONE compile) — but ONLY for
        # engines whose dispatcher would build the same plain step: the
        # onebit/compressed/host-step variants select different builders
        # under this key, and pre-seeding would silently disable them.
        key = ("train_step", gas)
        plain = not (getattr(eng, "_onebit_wire", False)
                     or getattr(eng, "_compressed", None)
                     or getattr(eng, "_host_runner", None))
        fn = eng._compiled.get(key)
        if fn is None:
            fn = eng._build_train_step(gas)
            if plain:
                fn = eng._compiled.setdefault(key, fn)
        # build a matching abstract batch
        import jax.numpy as jnp

        mb = eng.train_micro_batch_size() * eng.dp_world_size
        seq = getattr(eng.model_spec, "seq_len", None) or 128
        batch = {"tokens": jnp.zeros((gas, mb, seq), jnp.int32)}
        def train_step(s, b):   # named: the compile log records __name__
            return fn(s, b)

        with eng.mesh:
            costs = _cost_analysis(train_step, eng.state, batch)
        self.cost_analysis_unavailable = not cost_analysis_available(costs)
        return float(costs.get("flops", 0.0))

    # -- reporting -------------------------------------------------------- #
    def get_total_flops(self) -> float:
        return self.flops

    def get_total_duration(self) -> float:
        return self.elapsed

    def get_total_params(self) -> Optional[int]:
        return self.params

    def print_profile(self) -> None:
        tf = self.flops / 1e12
        print(f"flops per step: {tf:.3f} TF  params: {self.params}  "
              f"elapsed: {self.elapsed:.3f}s  "
              f"TF/s: {tf / self.elapsed if self.elapsed else 0:.2f}")


def get_model_profile(model_spec, batch_shape: Tuple[int, int],
                      as_string: bool = False):
    """Reference ``get_model_profile`` analog: (flops, macs≈flops/2, params)
    of one forward pass at the given (batch, seq) shape."""
    import jax.numpy as jnp

    params = model_spec.init_fn(jax.random.PRNGKey(0))
    tokens = jnp.zeros(batch_shape, jnp.int32)

    def model_forward(p, t):    # named: the compile log records __name__
        return model_spec.loss_fn(p, {"tokens": t})

    costs = profile_fn(model_forward, params, tokens)
    flops = costs["flops"]
    n_params = model_spec.num_params
    if as_string:
        return (f"{flops / 1e9:.2f} GFLOPs", f"{flops / 2e9:.2f} GMACs",
                f"{(n_params or 0) / 1e6:.2f} M")
    return flops, flops / 2, n_params
