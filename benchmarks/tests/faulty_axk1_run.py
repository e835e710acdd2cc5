"""A run of ``answer-axk1-steady`` with the generator broken underneath:
``--fault <name>`` plants one fault in the latent-attention programs, then the
rest of ``run.py`` runs as always, through the cell's own system module
(``systems/rag_answer_axk1.py``), and has to print ``correct: false``. At the
tiny CPU size of the rehearsal unless ``--on-chip`` is given, which leaves the
size, the device and the rate as the cell has them.

    python3 benchmarks/tests/faulty_axk1_run.py --fault no_group_limit --workload answer-axk1-steady

The decoder is the one ``answer-mistral4-steady`` serves, so its faults are
``faulty_mistral4_run.py``'s (``wrong_expert_share`` computes the pairs routed
to experts 12-23 with the held weights), and one of A.X-K1's own:
``no_group_limit`` chooses the top experts over the router's whole width, as if
there were one group. ``none`` plants nothing (the same entry has to print
``correct: true``).
"""

import dataclasses
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
from faulty_mistral4_run import FAULTS as SHARED_FAULTS, plant as plant_shared  # noqa: E402

FAULTS = SHARED_FAULTS + ("no_group_limit",)


def plant(fault: str) -> None:
    from pathway_tpu.models import mistral4

    if fault != "no_group_limit":
        return plant_shared(fault)
    routed = mistral4.routed_experts  # looked up by name when the two programs are first traced
    mistral4.routed_experts = lambda p, h, valid, cfg: routed(p, h, valid, dataclasses.replace(cfg, n_group=1,
                                                                                              topk_group=1))


if __name__ == "__main__":
    i = sys.argv.index("--fault")
    fault = sys.argv[i + 1]
    del sys.argv[i : i + 2]
    assert fault in FAULTS, fault
    if "--on-chip" in sys.argv:
        sys.argv.remove("--on-chip")
    else:
        sys.argv += ["--rehearse"]
        os.environ["JAX_PLATFORMS"] = "cpu"
    import run

    if fault != "none":
        plant(fault)
    code = run.main()
    sys.stdout.flush()
    os._exit(code)
