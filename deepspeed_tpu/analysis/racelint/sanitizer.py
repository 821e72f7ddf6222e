"""racelint's DYNAMIC half: an env-armable lockset/lock-order sanitizer.

The static half (``analysis/racelint``) proves what it can from source;
this module checks the residue at runtime, the way TSan/Eraser do:

* ``make_lock(name, reentrant=False)`` replaces bare ``threading.Lock()``
  at the control plane's construction sites.  Disarmed (the default) it
  is a thin passthrough — one module-global boolean test per acquire.
  Armed (``DSTPU_RACELINT=1`` in the environment, or :func:`arm` in
  process), every acquisition is recorded against the acquiring thread's
  held-lock stack:

  - **lock-order edges**: acquiring B while holding A records the
    directed edge A→B with BOTH acquisition stacks; an edge that closes
    a cycle in the accumulated graph is a deadlock finding naming the
    two paths — detected from the ORDER, so the test catches the bug
    without ever actually wedging;
  - **Eraser locksets**: :func:`note_access` intersects, per watched
    key, the set of locks held at each access once a second thread
    shows up; an empty intersection is a data-race finding with the
    last access stack from each side.

* Findings ACCUMULATE (a sanitizer that raises mid-test tears down the
  very interleaving being examined); tests drain them with
  :func:`findings` / :func:`assert_clean` and isolate with
  :func:`reset`.

The chaos acceptance tests (fleet / tenancy / guardian) run armed; the
seeded race + deadlock fixtures in ``tests/unit/test_racelint.py`` prove
the detector actually fires under the ``sync_point`` interleaving
fuzzer.

Stdlib-only, import-light: control-plane modules import this at module
scope, so it must not pull in anything heavy.
"""
from __future__ import annotations

import linecache
import os
import sys
import threading
from typing import Dict, FrozenSet, List, Optional, Set, Tuple

__all__ = [
    "make_lock", "arm", "disarm", "armed", "reset",
    "note_access", "watch_object",
    "findings", "assert_clean", "InstrumentedLock",
]

# --------------------------------------------------------------------- #
# global state — all tables below guarded by _state_lock, a RAW
# threading.Lock (the sanitizer must not instrument itself)
# --------------------------------------------------------------------- #
_state_lock = threading.Lock()
_armed = False
_env_checked = False

#: (outer lock name, inner lock name) -> (outer acq stack, inner acq stack)
_edges: Dict[Tuple[str, str], Tuple[str, str]] = {}
#: Eraser state per watched key
_locksets: Dict[str, dict] = {}
#: id(obj) -> registered name, for note_access(obj)
_watched: Dict[int, str] = {}
#: accumulated findings (dicts with "rule"/"message"/stack fields)
_findings: List[dict] = []
#: cycle edges already reported, so a hot loop reports once
_reported_cycles: Set[Tuple[str, str]] = set()


class _Held:
    """One entry of a thread's held-lock stack."""

    __slots__ = ("name", "stack", "count")

    def __init__(self, name: str, stack: str):
        self.name = name
        self.stack = stack
        self.count = 1


class _TLS(threading.local):
    def __init__(self):
        self.held: List[_Held] = []


_tls = _TLS()


_THIS_FILE = __file__


def _raw_stack(limit: int = 10) -> Tuple[Tuple[str, int, str], ...]:
    """Cheap stack capture for the per-acquisition hot path: walk
    ``sys._getframe`` collecting (file, line, func) tuples, sanitizer
    frames trimmed.  Formatting — and the linecache source lookup — is
    deferred to finding time (:func:`_format_stack`); armed acceptance
    tests acquire control-plane locks thousands of times and
    ``traceback.format_stack`` per acquire was most of the overhead."""
    frame = sys._getframe(1)
    out = []
    while frame is not None and len(out) < limit:
        code = frame.f_code
        if code.co_filename != _THIS_FILE:
            out.append((code.co_filename, frame.f_lineno, code.co_name))
        frame = frame.f_back
    out.reverse()
    return tuple(out)


def _format_stack(raw: Tuple[Tuple[str, int, str], ...]) -> str:
    lines = []
    for filename, lineno, func in raw:
        lines.append(f'  File "{filename}", line {lineno}, in {func}')
        src = linecache.getline(filename, lineno).strip()
        if src:
            lines.append(f"    {src}")
    return "\n".join(lines)


# --------------------------------------------------------------------- #
# arming
# --------------------------------------------------------------------- #
def armed() -> bool:
    """Whether the sanitizer records. The ``DSTPU_RACELINT`` environment
    variable is consulted once, lazily — set it before the process
    starts, or call :func:`arm` in-process (tests)."""
    global _armed, _env_checked
    if not _env_checked:
        with _state_lock:
            if not _env_checked:
                if os.environ.get("DSTPU_RACELINT", "") not in ("", "0"):
                    _armed = True
                _env_checked = True
    return _armed


def arm() -> None:
    """Arm in-process (idempotent). Locks made BEFORE arming are still
    instrumented — :func:`make_lock` always returns the wrapper and the
    wrapper checks the armed flag per acquisition."""
    global _armed, _env_checked
    with _state_lock:
        _armed = True
        _env_checked = True


def disarm(reset_state: bool = True) -> None:
    """Stop recording; by default also drop accumulated state so the
    next armed test starts clean."""
    global _armed, _env_checked
    with _state_lock:
        _armed = False
        _env_checked = True
    if reset_state:
        reset()


def reset() -> None:
    """Drop every recorded edge, lockset, and finding (test isolation).
    Per-thread held stacks are left alone — locks currently held stay
    tracked so their releases still balance."""
    with _state_lock:
        _edges.clear()
        _locksets.clear()
        _watched.clear()
        _findings.clear()
        _reported_cycles.clear()


# --------------------------------------------------------------------- #
# the instrumented lock
# --------------------------------------------------------------------- #
class InstrumentedLock:
    """Drop-in for ``threading.Lock``/``RLock`` that, when the sanitizer
    is armed, records lock-order edges and feeds the per-thread held set
    the Eraser checker intersects against."""

    __slots__ = ("name", "reentrant", "_inner")

    def __init__(self, name: str, reentrant: bool = False):
        self.name = name
        self.reentrant = reentrant
        self._inner = threading.RLock() if reentrant else threading.Lock()

    # -- core API ----------------------------------------------------- #
    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        record = armed()
        got = self._inner.acquire(blocking, timeout)
        if got and record:
            self._note_acquired()
        return got

    def release(self) -> None:
        if armed():
            self._note_released()
        self._inner.release()

    def __enter__(self) -> "InstrumentedLock":
        # unarmed (the production state) a ``with`` is the inner lock and
        # one global read: the registry takes this on every span and count
        if _env_checked and not _armed:
            self._inner.acquire()
        else:
            self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        if _env_checked and not _armed:
            self._inner.release()
        else:
            self.release()

    def locked(self) -> bool:
        inner_locked = getattr(self._inner, "locked", None)
        if inner_locked is not None:
            return inner_locked()
        if self._inner.acquire(False):   # RLock pre-3.14 has no locked()
            self._inner.release()
            return False
        return True

    def __repr__(self) -> str:
        kind = "rlock" if self.reentrant else "lock"
        return f"<InstrumentedLock {self.name!r} ({kind})>"

    # -- recording ---------------------------------------------------- #
    def _note_acquired(self) -> None:
        held = _tls.held
        if self.reentrant:
            for entry in held:
                if entry.name == self.name:   # re-entry: no new edge
                    entry.count += 1
                    return
        stack = _raw_stack()
        new_edges = [(entry.name, self.name, entry.stack)
                     for entry in held if entry.name != self.name]
        held.append(_Held(self.name, stack))
        if new_edges:
            with _state_lock:
                for outer, inner, outer_stack in new_edges:
                    if (outer, inner) not in _edges:
                        _edges[(outer, inner)] = (outer_stack, stack)
                        _check_cycle_locked(outer, inner)

    def _note_released(self) -> None:
        held = _tls.held
        for i in range(len(held) - 1, -1, -1):
            if held[i].name == self.name:
                held[i].count -= 1
                if held[i].count == 0:
                    del held[i]
                return
        # release of a lock this thread never recorded acquiring — the
        # sanitizer was armed mid-critical-section; ignore silently


def make_lock(name: str, reentrant: bool = False) -> InstrumentedLock:
    """The control plane's lock constructor.  Always returns the
    instrumented wrapper (so arming AFTER construction still works);
    the static half's lock inventory understands this factory too
    (``lockmodel._constructed_kind``), so converted sites keep their
    canonical identity in the lock-order graph."""
    return InstrumentedLock(name, reentrant=reentrant)


# --------------------------------------------------------------------- #
# runtime lock-order cycle detection
# --------------------------------------------------------------------- #
def _check_cycle_locked(outer: str, inner: str) -> None:
    """After recording edge outer→inner, report if inner already reaches
    outer through recorded edges (the new edge closes a cycle).  Caller
    holds ``_state_lock``."""
    if (outer, inner) in _reported_cycles:
        return
    # BFS from inner looking for outer, remembering the path
    parent: Dict[str, Tuple[str, str]] = {}   # node -> (pred, via edge key)
    frontier = [inner]
    seen = {inner}
    while frontier:
        nxt = []
        for node in frontier:
            for (a, b) in _edges:
                if a == node and b not in seen:
                    seen.add(b)
                    parent[b] = (a, f"{a} -> {b}")
                    nxt.append(b)
        frontier = nxt
        if outer in seen:
            break
    if outer not in seen:
        return
    # reconstruct inner -> ... -> outer, then the new edge closes it
    path = [outer]
    node = outer
    while node != inner:
        node = parent[node][0]
        path.append(node)
    path.reverse()   # inner, ..., outer
    cycle = " -> ".join(path + [inner])
    _reported_cycles.add((outer, inner))
    back_outer_stack, back_inner_stack = _edges[(path[0], path[1])] \
        if len(path) > 1 else _edges[(inner, outer)]
    new_outer_stack, new_inner_stack = _edges[(outer, inner)]
    _findings.append({
        "rule": "lock-order-cycle",
        "message": (f"lock-order cycle {cycle}: this thread acquired "
                    f"{inner!r} while holding {outer!r}, but another "
                    f"path acquires them in the opposite order"),
        "path_a": f"{outer} -> {inner}",
        "path_a_stacks": (_format_stack(new_outer_stack),
                          _format_stack(new_inner_stack)),
        "path_b": " -> ".join(path + [inner]),
        "path_b_stacks": (_format_stack(back_outer_stack),
                          _format_stack(back_inner_stack)),
    })


# --------------------------------------------------------------------- #
# Eraser-style lockset checking
# --------------------------------------------------------------------- #
def watch_object(obj: object, name: str) -> str:
    """Register ``obj`` so :func:`note_access` can be called with the
    object itself; returns the key used in findings."""
    with _state_lock:
        _watched[id(obj)] = name
    return name


def note_access(key, write: bool = True) -> None:
    """Record an access to watched shared state.  ``key`` is a string
    (the static half's inventory key, e.g.
    ``"telemetry/registry.py::MetricsRegistry._metrics"``) or an object
    previously registered via :func:`watch_object`.

    Eraser discipline: accesses by the FIRST thread constrain nothing
    (single-threaded init is fine unlocked); once a second thread
    touches the key, the candidate lockset is intersected with the locks
    held at every subsequent access — empty intersection ⇒ race."""
    if not armed():
        return
    if not isinstance(key, str):
        key = _watched.get(id(key), f"<unregistered object {type(key).__name__}>")
    held: FrozenSet[str] = frozenset(e.name for e in _tls.held)
    tid = threading.get_ident()
    stack = _raw_stack()
    with _state_lock:
        st = _locksets.get(key)
        if st is None:
            _locksets[key] = {"first": tid, "threads": {tid},
                              "lockset": None, "stacks": {tid: stack},
                              "reported": False}
            return
        st["threads"].add(tid)
        st["stacks"][tid] = stack
        if len(st["threads"]) < 2:
            return   # still exclusive to the first thread
        if st["lockset"] is None:
            st["lockset"] = set(held)
        else:
            st["lockset"] &= held
        if not st["lockset"] and not st["reported"]:
            st["reported"] = True
            others = [t for t in st["threads"] if t != tid]
            other_stack = st["stacks"].get(others[0], "") if others else ""
            _findings.append({
                "rule": "lockset-race",
                "message": (f"{key}: accessed from {len(st['threads'])} "
                            "threads with NO lock held in common"),
                "key": key,
                "stack_a": _format_stack(stack),
                "stack_b": _format_stack(other_stack),
            })


# --------------------------------------------------------------------- #
# reporting
# --------------------------------------------------------------------- #
def findings() -> List[dict]:
    """Snapshot of accumulated findings (does not clear — see reset)."""
    with _state_lock:
        return [dict(f) for f in _findings]


def render(fs: Optional[List[dict]] = None) -> str:
    fs = findings() if fs is None else fs
    out = []
    for f in fs:
        out.append(f"[{f['rule']}] {f['message']}")
        if f["rule"] == "lock-order-cycle":
            out.append(f"  path A ({f['path_a']}) acquired at:\n"
                       + _indent(f["path_a_stacks"][1]))
            out.append(f"  path B ({f['path_b']}) acquired at:\n"
                       + _indent(f["path_b_stacks"][1]))
        elif f["rule"] == "lockset-race":
            if f.get("stack_a"):
                out.append("  one side:\n" + _indent(f["stack_a"]))
            if f.get("stack_b"):
                out.append("  other side:\n" + _indent(f["stack_b"]))
    return "\n".join(out)


def _indent(text: str, pad: str = "    ") -> str:
    return "\n".join(pad + ln for ln in text.splitlines())


def assert_clean() -> None:
    """Raise AssertionError rendering every accumulated finding — the
    chaos acceptance tests' final gate."""
    fs = findings()
    if fs:
        raise AssertionError(
            f"racelint sanitizer: {len(fs)} finding(s)\n" + render(fs))
