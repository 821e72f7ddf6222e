"""What the three ``test_chip_compile*.py`` files share: the described v5e
they compile for, and the Mosaic calls of a compiled program. A plain
module; the fixture is imported by name into each file, so only a pytest
worker that is handed one of them loads libtpu."""
import os
import re

import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])




def mosaic_calls(text):
    """The Mosaic calls of a compiled program as a trace would name them:
    the instruction's name, and its text with the operands' types (which
    the compiled text keeps under ``operand_layout_constraints``)."""
    from benchmarks.trace_reduce import Op

    calls = []
    for line in text.splitlines():
        if "custom_call_target=\"tpu_custom_call\"" not in line:
            continue
        name = line.split("=", 1)[0].strip().lstrip("%")
        calls.append(Op(name, "custom-call", re.sub(
            r"custom-call\(.*?\), (.*operand_layout_constraints=\{(.+?\})\}, )",
            r"custom-call(\2), \1", line), 0.0, 0.0))
    return calls

