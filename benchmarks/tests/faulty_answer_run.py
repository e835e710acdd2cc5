"""A run of ``answer-lfm2-steady`` with the generator broken underneath:
``--fault <name>`` plants one fault in the program's decode step, then the rest
of ``run.py`` runs as always, through the cell's own system module
(``systems/rag_answer.py``), and has to print ``correct: false``. At the tiny
CPU size of the rehearsal unless ``--on-chip`` is given, which leaves the size,
the device and the rate as the cell has them.

    python3 benchmarks/tests/faulty_answer_run.py --fault stale_conv_tail --workload answer-lfm2-steady

Faults a slot-based generator can have, each in the state a request keeps
between device calls: ``stale_conv_tail`` never moves a slot's convolution tail
on (every step convolves over the prompt's last two inputs);
``cache_off_by_one`` reads a slot's keys one position off (every step attends
with each key moved one position on, its value left where it was);
``neighbour_slot_keys`` shows only where slots are live together: in a step
that advances more than one row, every row uses the key cache of the slot
before its own (a request alone in its steps gets its own tokens); ``none``
plants nothing (the same entry has to print ``correct: true``).
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
FAULTS = ("none", "stale_conv_tail", "cache_off_by_one", "neighbour_slot_keys")


def plant(fault: str) -> None:
    from pathway_tpu.models import lfm2

    inner = lfm2.decode_logits  # looked up by name when ``lm_decode`` is first traced

    def decode_logits(params, state, active, cfg):
        import jax.numpy as jnp

        if fault == "stale_conv_tail":
            new_state, logits, touched = inner(params, state, active, cfg)
            return dict(new_state, tail=state["tail"]), logits, touched
        if fault == "cache_off_by_one":
            return inner(params, dict(state, k=[jnp.roll(k, 1, axis=1) for k in state["k"]]), active, cfg)
        # the key cache's slot index one off, for reading and writing alike, in steps of several rows only
        shift = jnp.where(jnp.sum(active) > 1, 1, 0)
        new_state, logits, touched = inner(
            params, dict(state, k=[jnp.roll(k, shift, axis=0) for k in state["k"]]), active, cfg)
        return dict(new_state, k=[jnp.roll(k, -shift, axis=0) for k in new_state["k"]]), logits, touched

    lfm2.decode_logits = decode_logits


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
