"""Largest share of the engine's sequence slots (rings and recurrent state
of a model that keeps them per sequence) held at one time
(``fastgen_state_slots_in_use_peak``, the engine's own high-water mark at
the end of the window) over the slots the cell's engine was given. Nothing
to read where the program has no such gauge or the engine no slots.
"""


def read(run):
    slots = (run.extras.get("engine") or {}).get("state_slots")
    if run.telemetry is None or not slots:
        return None
    peak = run.telemetry.gauge("fastgen_state_slots_in_use_peak")
    return None if peak is None else 100.0 * peak / slots
