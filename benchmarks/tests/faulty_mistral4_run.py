"""A run of ``answer-mistral4-steady`` with the generator broken underneath:
``--fault <name>`` plants one fault in the ``mistral4`` programs, then the rest
of ``run.py`` runs as always, through the cell's own system module
(``systems/rag_answer_mistral4.py``), and has to print ``correct: false``. At the
tiny CPU size of the rehearsal unless ``--on-chip`` is given, which leaves the
size, the device and the rate as the cell has them.

    python3 benchmarks/tests/faulty_mistral4_run.py --fault latent_off_by_one --workload answer-mistral4-steady

Faults this generator can have, in what a request keeps between device calls and
in the share it is told it holds: ``latent_off_by_one`` reads a slot's latents
one position off in every step (each ``ckv`` moved one position on, its ``kr``
left where it was); ``neighbour_slot_latent`` shows only where slots are live
together: in a step that advances more than one row, every row uses the latent
cache of the slot before its own; ``kr_unrotated`` caches the shared key as it
comes out of ``Wdkv``, without RoPE, in both programs; ``no_shared_expert``
leaves the shared expert out; ``wrong_expert_share`` computes, with the held
weights, the pairs routed to the share after this chip's (experts 32-63 in
place of 0-31); ``none`` plants nothing (the same entry has to print
``correct: true``).
"""

import dataclasses
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
FAULTS = ("none", "latent_off_by_one", "neighbour_slot_latent", "kr_unrotated", "no_shared_expert",
          "wrong_expert_share")


def plant(fault: str) -> None:
    import jax.numpy as jnp

    from pathway_tpu.models import mistral4

    # each is looked up by name when the two programs are first traced
    decode, latents, routed = mistral4.decode_logits, mistral4._latents, mistral4.routed_experts

    def decode_logits(params, state, active, cfg):
        if fault == "latent_off_by_one":
            new_state, logits, counts = decode(params, dict(state, ckv=[jnp.roll(c, 1, axis=1) for c in state["ckv"]]),
                                               active, cfg)
            return dict(new_state, ckv=[jnp.roll(c, -1, axis=1) for c in new_state["ckv"]]), logits, counts
        # the latent cache's slot index one off, for reading and writing alike, in steps of several rows only
        shift = jnp.where(jnp.sum(active) > 1, 1, 0)
        new_state, logits, counts = decode(
            params, dict(state, ckv=[jnp.roll(c, shift, axis=0) for c in state["ckv"]]), active, cfg)
        return dict(new_state, ckv=[jnp.roll(c, -shift, axis=0) for c in new_state["ckv"]]), logits, counts

    def unrotated(p, h, positions, cfg):
        return latents(p, h, jnp.zeros_like(positions), cfg)  # position 0 turns nothing

    if fault in ("latent_off_by_one", "neighbour_slot_latent"):
        mistral4.decode_logits = decode_logits
    elif fault == "kr_unrotated":
        mistral4._latents = unrotated
    elif fault == "no_shared_expert":
        mistral4.shared_expert = lambda p, h: jnp.zeros_like(h)
    elif fault == "wrong_expert_share":
        mistral4.routed_experts = lambda p, h, valid, cfg: routed(
            p, h, valid, dataclasses.replace(cfg, first_expert=cfg.first_expert + cfg.n_routed_experts))


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
