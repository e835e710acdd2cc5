"""``run.py --rehearse`` with the second system's timed path broken underneath:
the REST connector hands every reply that is a list over in reverse (an answer
altered where it is produced, in the program and not in the system's module).
The run has to print ``correct: false``.

    python3 benchmarks/tests/second_system/plant_fault.py --workload top-words-steady --seed 1 --seconds 2
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

if __name__ == "__main__":
    sys.argv.append("--rehearse")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import run
    from pathway_tpu.io.http import _server

    jsonable = _server._jsonable

    def reversed_lists(value):
        out = jsonable(value)
        return out[::-1] if isinstance(out, list) else out

    _server._jsonable = reversed_lists
    code = run.main()
    sys.stdout.flush()
    os._exit(code)
