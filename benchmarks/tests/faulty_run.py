"""A rehearsal run with the timed path broken underneath: ``--fault <name>`` plants
one fault in the program, then the rest of ``run.py`` runs as always (at the tiny
CPU size, skipping only the look for a chip), through the cell's own system
module (``systems/vector_store.py``), and has to print ``correct: false``.

    python3 benchmarks/tests/faulty_run.py --fault shifted_ranks --workload serve-dense-2m

Faults a one-chip serving cell can have: an answer altered where it is produced.
``shifted_ranks`` rolls each reply's ids by one rank inside the index's search;
``swapped_queries`` answers each query of a batch with its neighbour's result;
``live_rows_only`` searches the live documents and leaves every resident row out
(the rows a faster scan would be tempted to skip); ``last_block_only`` leaves out
every row before the last install block; ``none`` plants nothing (the same entry
has to print ``correct: true``).
"""

import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
FAULTS = ("none", "shifted_ranks", "swapped_queries", "live_rows_only", "last_block_only")


def plant(fault: str, first_kept_slot: int) -> None:
    from pathway_tpu.ops import knn

    inner = knn.DenseKNNStore.search_batch

    def search_batch(self, queries, k):
        if fault in ("live_rows_only", "last_block_only"):
            # the top-k of the slots from ``first_kept_slot`` on: search wide, keep those
            scores, idx, valid = inner(self, queries, min(len(self), 2048))
            keep = np.argsort(~((idx >= first_kept_slot) & valid), axis=1, kind="stable")[:, :k]
            return tuple(np.take_along_axis(a, keep, axis=1) for a in (scores, idx, valid))
        scores, idx, valid = inner(self, queries, k)
        if fault == "shifted_ranks":
            idx = np.roll(idx, -1, axis=1)
        elif fault == "swapped_queries" and len(idx) > 1:
            scores, idx, valid = (np.roll(a, 1, axis=0) for a in (scores, idx, valid))
        return scores, idx, valid

    knn.DenseKNNStore.search_batch = search_batch


if __name__ == "__main__":
    i = sys.argv.index("--fault")
    fault = sys.argv[i + 1]
    del sys.argv[i : i + 2]
    assert fault in FAULTS, fault
    sys.argv += ["--rehearse"]
    os.environ["JAX_PLATFORMS"] = "cpu"
    import run

    if fault != "none":
        corpus = run.resolve(sys.argv[sys.argv.index("--workload") + 1], True)["config"]["corpus"]
        # resident rows take the first slots, block by block; the live documents the last
        n_res, block = int(corpus["resident_rows"]), int(corpus["install_block_rows"])
        plant(fault, n_res if fault == "live_rows_only" else (n_res - 1) // block * block)
    code = run.main()
    sys.stdout.flush()
    os._exit(code)
