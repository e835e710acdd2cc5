"""A run of ``answer-falconh1-steady`` with the generator broken underneath:
``--fault <name>`` plants one fault in the ``falcon_h1`` programs, then the rest
of ``run.py`` runs as always, through the cell's own system module
(``systems/rag_answer_falconh1.py``), and has to print ``correct: false``. At the
tiny CPU size of the rehearsal unless ``--on-chip`` is given, which leaves the
size, the device and the rate as the cell has them.

    python3 benchmarks/tests/faulty_falconh1_run.py --fault padding_feeds_the_state --workload answer-falconh1-steady

Faults this generator can have, in the third kind of state a slot keeps and in
its multipliers: ``ssm_state_not_advanced`` leaves every slot's state-space state
as its prefill left it (a step reads it and writes nothing back);
``neighbour_slot_state`` shows only where slots are live together: in a step
that advances more than one row, every row uses the state-space state of the
slot before its own; ``padding_feeds_the_state`` gives the padded places of a
prefill bucket a step of their own (``dt`` 1 where it has to be 0), so the
state a slot gets is the state after the bucket, not after the prompt's last
token; ``stale_tail`` never shifts the convolution's tail in a step;
``mup_segments_shifted`` lays ``ssm_multipliers`` one segment on (z's over x, x's
over B, ...); ``none`` plants nothing (the same entry has to print ``correct:
true``).
"""

import dataclasses
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
FAULTS = ("none", "ssm_state_not_advanced", "neighbour_slot_state", "padding_feeds_the_state", "stale_tail",
          "mup_segments_shifted")
# at the rehearsal's size (a state of 16, steps of 0.1-1) the state forgets within a few tokens, so a wrong state
# after the prefill heals within the first of a reply's 128 tokens: 0.001-0.011 by the means and 0.63-1.73 by the
# maxima from one window to the next, beside limits of 0.03 and 0.5, too near to pin. At the cell's size the state
# remembers for tens to hundreds of tokens and ``--on-chip`` read 2.43 / 7.53 (PERF.md section 6, PR 35);
# ``tests/test_falcon_h1.py`` holds the padded bucket's state to the reference's exactly.
SEEN_AT_THE_CELLS_SIZE_ONLY = ("padding_feeds_the_state",)


def plant(fault: str) -> None:
    import jax.numpy as jnp

    from pathway_tpu.models import falcon_h1

    # each is looked up by name when the two programs are first traced
    decode, scan, mup = falcon_h1.decode_logits, falcon_h1.ssd_scan, falcon_h1._mup

    def decode_logits(params, state, active, cfg):
        if fault == "neighbour_slot_state":
            # every row reads the state of the slot before its own and writes what it makes of it into its own, in steps of several rows only
            shift = jnp.where(jnp.sum(active) > 1, 1, 0)
            return decode(params, dict(state, ssm=[jnp.roll(s, shift, axis=0) for s in state["ssm"]]), active, cfg)
        new_state, logits, counts = decode(params, state, active, cfg)
        kept = "ssm" if fault == "ssm_state_not_advanced" else "tail"
        return dict(new_state, **{kept: state[kept]}), logits, counts

    if fault in ("ssm_state_not_advanced", "neighbour_slot_state", "stale_tail"):
        falcon_h1.decode_logits = decode_logits
    elif fault == "padding_feeds_the_state":
        falcon_h1.ssd_scan = lambda x, dt, *rest: scan(x, jnp.where(dt == 0.0, 1.0, dt), *rest)
    elif fault == "mup_segments_shifted":
        falcon_h1._mup = lambda cfg: mup(dataclasses.replace(
            cfg, ssm_multipliers=cfg.ssm_multipliers[-1:] + cfg.ssm_multipliers[:-1]))


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
