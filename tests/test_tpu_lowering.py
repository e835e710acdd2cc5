"""The device programs on the serving path must LOWER for a TPU — checked here on
the CPU, in seconds, with ``jax.export`` and ``platforms=["tpu"]``.

Lowering is the first gate only: it catches what the Pallas→Mosaic translation
refuses (a block shape whose last two dims are neither multiples of (8, 128) nor
the full dimension, an op with no TPU rule). Mosaic's own VMEM and tiling checks
run when the chip compiles the program; ``chip_smoke.py`` is that check.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest

from pathway_tpu.ops.knn_ivf import PAGE

S = jax.ShapeDtypeStruct


def _export_tpu(fn, *args):
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    assert exported.platforms == ("tpu",)
    return exported


@pytest.mark.parametrize("packed_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("metric", ["cos", "l2sq"])
def test_ivf_pallas_kernel_lowers_for_tpu(packed_dtype, metric):
    """The shapes VectorStoreServer(index_factory="ivf") produces: d = 384,
    queries padded to 8, 128-row pages, f32 and bf16 packed corpus."""
    from pathway_tpu.ops.knn_ivf import _ivf_query_fused

    d, q, n_clusters, n_pages = 384, 8, 64, 64
    f32, i32 = jnp.float32, jnp.int32
    fn = functools.partial(
        _ivf_query_fused, k=8, n_probe=8, max_pages=2, metric=metric, impl="pallas"
    )
    exported = _export_tpu(
        fn,
        S((n_clusters, d), f32),  # centroids
        S((n_clusters,), i32),  # first_page
        S((n_clusters,), i32),  # n_pages
        S((n_pages * PAGE, d), packed_dtype),  # packed
        S((n_pages, PAGE), f32),  # pn
        S((n_pages, PAGE), f32),  # pm
        S((n_pages * PAGE,), i32),  # packed_rows
        S((q, d), f32),  # queries
    )
    assert "tpu_custom_call" in exported.mlir_module()  # the Mosaic kernel is in it


def test_dense_search_kernel_lowers_for_tpu():
    from pathway_tpu.ops.knn import _search_kernel

    cap, d, q = 4096, 384, 8
    fn = functools.partial(_search_kernel, k=8, metric="cos")
    _export_tpu(
        fn,
        S((cap, d), jnp.float32),
        S((cap,), jnp.bool_),
        S((cap,), jnp.float32),
        S((q, d), jnp.float32),
    )


def test_encoder_forward_lowers_for_tpu():
    """``_encode_ids`` with its float16 output cast and exact gelu, on the tiny
    test architecture (the op set is the full-width model's)."""
    from pathway_tpu.models.encoder import EncoderConfig, JaxSentenceEncoder

    tiny = EncoderConfig(
        vocab_size=8192, hidden_size=64, num_layers=2, num_heads=4, intermediate_size=128
    )
    enc = JaxSentenceEncoder("pw-test-tiny", config=tiny, max_length=64)
    exported = _export_tpu(enc._encode_ids, enc.params, S((8, 16), jnp.int32))
    assert exported.out_avals[0].shape == (8, 64)


def _assert_batched_products(text, layers, rows_in, rows_mid):
    """In ``layers`` expert layers a conditional whose one branch holds three
    batched products from a padded buffer (``rows_in`` into w1 and w3,
    ``rows_mid`` into w2) and whose other holds the grouped ones; with 0, neither."""
    # the conditional's branches by their places in the text: the false one grouped, the true one batched
    assert ("/moe_experts/cond/branch_0_fun/ragged_dot" in text) == ("/moe_experts/cond/branch_1_fun/" in text)
    assert ("/moe_experts/cond/" in text) == bool(layers) and ("/moe_experts/ragged_dot" in text) == (not layers)
    assert text.count(f"(tensor<{rows_in}>, ") == 2 * layers and text.count(f"(tensor<{rows_mid}>, ") == layers


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_generators_programs_lower_for_tpu_at_published_width(program):
    """``lm_decode`` and ``lm_prefill`` of the ``lfm2_moe`` decoder at LFM2-8B-A1B's
    widths (hidden 2,048, 32 experts of 1,792, top 4, vocabulary 65,536), one
    period of its layer pattern, 16 slots: shapes only, nothing is allocated.
    A step's expert products are one grouped product each (XLA's ragged dot) and
    nothing else; a prefill's 256 tokens give an expert 32 rows, so each of its
    four expert layers holds a conditional over the batched products (32 experts
    x 128 places) and the grouped ones (``models/moe.py``)."""
    from pathway_tpu.models import lfm2

    cfg = lfm2.Lfm2Config(num_hidden_layers=6, layer_types=lfm2.PUBLISHED_LAYER_TYPES[:6])
    params = lfm2.param_shapes(cfg)
    state = jax.eval_shape(lambda: lfm2.init_state(cfg, 16, 1088))
    if program == "decode":
        exported = _export_tpu(functools.partial(lfm2.decode_logits, cfg=cfg), params, state, S((16,), jnp.bool_))
    else:
        exported = _export_tpu(functools.partial(lfm2.prefill_logits, cfg=cfg), params, state,
                               S((256,), jnp.int32), S((), jnp.int32), S((), jnp.int32))
    text = exported.mlir_module()
    assert text.count("@chlo.ragged_dot(") == 3 * 4  # w1, w3, w2 of the four expert layers
    _assert_batched_products(text, 4 if program == "prefill" else 0, "32x128x2048xbf16", "32x128x1792xbf16")


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_mistral4_programs_lower_for_tpu_at_published_width(program):
    """``lm_decode`` (absorbed) and ``lm_prefill`` (expanded) of the ``mistral4``
    decoder at Mistral-Small-4's widths (hidden 4,096, ranks 1,024 and 256, 32
    heads of 64 + 64 and 128, 32 held experts of 2,048 under a router of 128,
    32,768 vocabulary rows), two layers, 16 slots of 2,112 positions: shapes
    only, nothing is allocated. The step never expands a key or a value: no
    product of its module has the cache's positions beside a head's key size."""
    from pathway_tpu.models import mistral4

    cfg = mistral4.Mistral4Config(num_hidden_layers=2, n_routed_experts=32, n_router_experts=128, vocab_size=32768)
    params = mistral4.param_shapes(cfg)
    state = jax.eval_shape(lambda: mistral4.init_state(cfg, 16, 2112))
    assert state["ckv"][0].shape == (16, 2112, 256) and state["kr"][0].shape == (16, 2112, 64)
    if program == "decode":
        exported = _export_tpu(functools.partial(mistral4.decode_logits, cfg=cfg), params, state, S((16,), jnp.bool_))
        assert "16x2112x32x" not in exported.mlir_module()  # no per-head keys or values over the cache
    else:
        exported = _export_tpu(functools.partial(mistral4.prefill_logits, cfg=cfg), params, state,
                               S((1536,), jnp.int32), S((), jnp.int32), S((), jnp.int32))
        assert "1536x32x192" in exported.mlir_module()  # keys and values per head, from the latent
        assert "tpu_custom_call" in exported.mlir_module()  # over them the flash kernel, not 32 x 1,536 x 1,536 scores
        assert "32x1536x1536" not in exported.mlir_module()
    text = exported.mlir_module()
    assert text.count("@chlo.ragged_dot(") == 3 * 2  # w1, w3, w2 of both layers
    # 1,536 tokens x 4 over the router's 128 give a held expert 48 rows and 192 places; a step's 16 x 4 give it half a row
    _assert_batched_products(text, 2 if program == "prefill" else 0, "32x192x4096xbf16", "32x192x2048xbf16")


@pytest.mark.parametrize("program", ["decode", "prefill_256", "prefill_512", "prefill_1024"])
def test_the_falcon_h1_programs_lower_for_tpu_at_published_width(program, monkeypatch):
    """``lm_decode`` (the recurrence) and ``lm_prefill`` (the chunked scan, each
    bucket) of the ``falcon_h1`` decoder at Falcon-H1-34B's widths (hidden 5,120,
    32 state-space heads of 128 with a state of 256 in 2 groups, 20 query and 4
    key/value heads of 128, a SwiGLU of 21,504, vocabulary 261,120), the cell's
    six blocks, 32 slots of 1,152 positions: shapes only, nothing is allocated.
    The step's recurrence is the ``ssm_step`` kernel of each block, as the chip
    runs it (the backend the program asks for is the TPU here)."""
    from pathway_tpu.models import falcon_h1

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    cfg = falcon_h1.FalconH1Config(num_hidden_layers=6)
    params = falcon_h1.param_shapes(cfg)
    state = jax.eval_shape(lambda: falcon_h1.init_state(cfg, 32, 1152))
    assert state["ssm"][0].shape == (32, 32, 128, 256) and state["tail"][0].shape == (32, 3, 5120)
    if program == "decode":
        text = _export_tpu(functools.partial(falcon_h1.decode_logits, cfg=cfg), params, state,
                           S((32,), jnp.bool_)).mlir_module()
        assert "ssd_scan" not in text  # the step is the recurrence itself
        assert text.count("tpu_custom_call") == 6 and "ssm_step" in text  # in one kernel a block
        assert "32x2x16x128x256xf32" not in text  # and not in XLA over every slot's state
    else:
        bucket = int(program.split("_")[1])
        text = _export_tpu(functools.partial(falcon_h1.prefill_logits, cfg=cfg), params, state,
                           S((bucket,), jnp.int32), S((), jnp.int32), S((), jnp.int32)).mlir_module()
        chunks = bucket // 128
        assert "ssd_scan" in text and f"tensor<{chunks}x2x16x128x128xf32>" in text  # the decays inside each chunk
        assert f"tensor<{chunks}x2x16x128x256xf32>" in text  # a chunk's own state, a head: (d_head, state)
    assert "ssm_op" in text and "attn_op" in text and "mlp_op" in text
    assert "5120x261120xbf16" in text  # the untied head, whole
