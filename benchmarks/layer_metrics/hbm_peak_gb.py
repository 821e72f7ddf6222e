"""Peak bytes held on the fullest chip by the program alone: engine,
weights, cache pool, and the temporaries of the programs of warm-up and
window (``device.memory_peak_bytes``: arrays plus what the runtime set
aside for programs), without the float32 reference comparison the
benchmark runs on the same device (``device.program_peak_bytes``). The
line's ``device.memory_peak_bytes`` is the most the whole process is known
to have held at one time.
"""


def read(run):
    peak = run.extras.get("program_peak_bytes")
    return None if not peak else peak / 1e9
