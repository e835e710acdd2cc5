"""Quantized retrieval tower: per-page symmetric int8 rows with an exact
fp32 rescore epilogue (ROADMAP item 5, the last retrieve-hot-path lever).

The PR-15 tiered store keeps every tier in fp32, so the
``PATHWAY_IVF_HBM_BUDGET_MB`` hot tier holds ~4x fewer documents than the
same bytes could. This module supplies the quantization layer the tiered
store (``ops/knn_tiers.py``) threads through host blocks, hot mirrors and
the frozen spill tier:

- **Per-page symmetric int8.** Each 128-row page (the PR-1 residency unit)
  carries one fp32 scale (``max|v| / 127``) and a zero-point slot (always
  ``0.0`` for the symmetric int8 scheme; the field exists so the reserved
  asymmetric/fp8 formats extend the sidecar, not the protocol) — the same
  shape paged-attention kernels use for per-page KV state.
- **Exact integer dot products.** The approximate pass accumulates the int8
  dot in float32 BLAS over the *cast* codes: every product is an integer
  ``<= 127^2`` and every partial sum stays below ``2^24`` for ``dim <=
  1024``, so f32 accumulation is EXACT whatever the accumulation order —
  which is precisely why hot/cold/spill residency stays bitwise-invariant
  under int8 without a parity ceremony (``_INT8_EXACT_DIM_LIMIT`` guards
  the bound; larger dims fall back to int32 accumulation).
- **Exact fp32 rescore epilogue.** The int8 pass only builds a
  ``PATHWAY_IVF_RESCORE_K``-deep shortlist; the scores a search RETURNS are
  recomputed from the fp32 source rows through :func:`rescore_pairs` — THE
  pinned epilogue the store and the tests share, so
  "returned scores are exact" holds by construction and a stale sidecar or
  a wrong gather is a bitwise diff, not a silent recall drop.

The fp32 rows remain the source of truth everywhere (export, rebuild,
descriptor replication, the rescore pass); int8 is a *derived mirror*, and
every derivation site is deterministic round-to-nearest (stochastic
rounding is a training trick — retrieval wants replayable bits).

Device kernels (:func:`quant_probe_kernel` / :func:`quant_score_block_kernel`)
are module-level jitted functions registered in ``kernel_cache_sizes()``
beside ``tiered_assign``/``tiered_score``; both take pow2-bucketed shapes so
their jit caches stay O(log) like every other search kernel.
"""

from __future__ import annotations

import functools
import os
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

PAGE = 128  # one scale/zero-point pair per 128-row page (the residency unit)

#: largest dim for which the f32-accumulated int8 dot is exact: every partial
#: sum is an integer bounded by dim * 127^2 and f32 represents integers up to
#: 2^24 exactly, so accumulation order cannot change the result
_INT8_EXACT_DIM_LIMIT = (1 << 24) // (127 * 127)


class QuantConfigError(RuntimeError):
    """Typed misconfiguration of the quantized tower (unknown or reserved
    ``PATHWAY_IVF_QUANT`` mode, replica mode mismatch) — callers triage by
    type, never by repr."""


def quant_mode(raw: "str | None" = None) -> str:
    """Resolve the quantization mode: ``off`` (default) or ``int8``.

    ``fp8`` is a RESERVED mode (the sidecar format carries zero-points for
    it) — asking for it is a typed refusal, not a silent fp32 fallback, and
    so is any unknown value: a typo'd mode silently serving full precision
    would defeat the budget the operator thinks they configured."""
    if raw is None:
        raw = os.environ.get("PATHWAY_IVF_QUANT", "off")
    mode = (raw or "off").strip().lower()
    if mode in ("off", "0", "false", "no", "none", ""):
        return "off"
    if mode == "int8":
        return "int8"
    if mode == "fp8":
        raise QuantConfigError(
            "PATHWAY_IVF_QUANT=fp8 is reserved: the sidecar format supports "
            "it but no fp8 kernel ships yet — use int8 or off"
        )
    raise QuantConfigError(
        f"unknown PATHWAY_IVF_QUANT mode {raw!r}: expected off|int8 (fp8 reserved)"
    )


def rescore_k() -> int:
    """``PATHWAY_IVF_RESCORE_K``: exact-rescore shortlist depth (default 64).
    The effective depth is ``max(k, PATHWAY_IVF_RESCORE_K)`` clamped to the
    candidate count — the shortlist can never be shallower than the answer."""
    try:
        return max(1, int(os.environ.get("PATHWAY_IVF_RESCORE_K", "") or 64))
    except ValueError:
        return 64


# ---------------------------------------------------------------------------
# per-page quantization (host, deterministic)
# ---------------------------------------------------------------------------


def page_scale(rows: np.ndarray) -> float:
    """Symmetric scale of one page: ``max|v| / 127`` (1.0 for an all-zero
    page so dequantization stays well-defined)."""
    m = float(np.max(np.abs(rows))) if rows.size else 0.0
    return (m / 127.0) if m > 0.0 else 1.0


def quantize_rows(rows: np.ndarray, scale: float) -> np.ndarray:
    """Round-to-nearest int8 codes of ``rows`` at ``scale`` (clipped to
    [-127, 127]; -128 is never produced so negation stays closed)."""
    return np.clip(np.rint(rows / np.float32(scale)), -127, 127).astype(np.int8)


def quantize_block(
    vecs: np.ndarray, pages: "range | np.ndarray | None" = None
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Quantize a (cap, dim) block per page. Returns ``(qvecs int8 (cap,
    dim), qscale f32 (cap // PAGE,), qzero f32 (cap // PAGE,))``; ``pages``
    limits the work to the named page indices (the append/recalibrate hook —
    untouched pages keep their existing codes when the caller splices)."""
    cap = vecs.shape[0]
    n_pages = max(1, cap // PAGE)
    qvecs = np.zeros((cap, vecs.shape[1]), dtype=np.int8)
    qscale = np.ones(n_pages, dtype=np.float32)
    qzero = np.zeros(n_pages, dtype=np.float32)
    todo = range(n_pages) if pages is None else pages
    for p in todo:
        lo, hi = p * PAGE, min((p + 1) * PAGE, cap)
        if lo >= cap:
            continue
        s = page_scale(vecs[lo:hi])
        qscale[p] = np.float32(s)
        qvecs[lo:hi] = quantize_rows(vecs[lo:hi], s)
    return qvecs, qscale, qzero


def row_scales(qscale: np.ndarray, cap: int) -> np.ndarray:
    """Broadcast (n_pages,) page scales to (cap,) per-row scales."""
    return np.repeat(qscale, PAGE)[:cap].astype(np.float32)


# ---------------------------------------------------------------------------
# int8 scoring (host path — exact integer dots, order-invariant)
# ---------------------------------------------------------------------------


def quantize_queries(q: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Per-row symmetric int8 query codes: ``(codes int8 (nq, dim), scales
    f32 (nq,))``. Queries that already sit on the int8 lattice (the
    encoder's quantized tower) re-quantize with ZERO extra rounding error —
    the row max is itself a lattice point, so the scale reproduces."""
    q = np.asarray(q, dtype=np.float32)
    m = np.max(np.abs(q), axis=1)
    scales = np.where(m > 0.0, m / 127.0, 1.0).astype(np.float32)
    codes = np.clip(np.rint(q / scales[:, None]), -127, 127).astype(np.int8)
    return codes, scales


def int8_dot(q_codes: np.ndarray, d_codes: np.ndarray) -> np.ndarray:
    """Exact (nq, rows) integer dot of int8 code matrices. For ``dim <=
    _INT8_EXACT_DIM_LIMIT`` the codes are cast to f32 and accumulated
    through BLAS — every partial sum is an exactly-representable integer, so
    the result is bit-identical to integer accumulation in ANY order (this
    is what makes residency moves bitwise-invariant under int8 without a
    per-tier parity probe). Larger dims accumulate in int32.

    Accepts pre-cast f32 code matrices too (``copy=False`` makes the cast a
    no-op), so callers holding a cached cast skip the per-call copy."""
    if q_codes.shape[1] <= _INT8_EXACT_DIM_LIMIT:
        return (
            q_codes.astype(np.float32, copy=False)
            @ d_codes.astype(np.float32, copy=False).T
        )
    return (
        q_codes.astype(np.int32) @ d_codes.astype(np.int32).T
    ).astype(np.float32)


def approx_scores(
    q_codes: np.ndarray,
    q_scales: np.ndarray,
    qn: np.ndarray,
    d_codes: np.ndarray,
    d_row_scales: np.ndarray,
    d_norms: np.ndarray,
    metric: str,
    maskadd: "np.ndarray | None" = None,
    negnorm: "np.ndarray | None" = None,
) -> np.ndarray:
    """Approximate metric scores from int8 codes: the dequantized dot rides
    the SAME metric epilogue shape as the exact path, with the exact fp32
    norms (stored anyway — only the cross-term is approximate). Shortlist
    builder ONLY: returned scores never leave the store (the rescore pass
    replaces them).

    The epilogue runs in place on the dot buffer, and for l2sq the 2x folds
    into the query scales up front — multiplying by an exact power of two
    commutes through f32 products bit-for-bit, so the values stay identical
    to the device kernel's ``2.0 * (dot * (qs x srow)) - ...`` order while
    the host pays one pass fewer per block. ``maskadd`` (0/-inf additive
    validity, the device-mirror mask contract) folds dead-row masking into
    one vector add. ``negnorm`` (l2sq only) is the caller's pre-fused
    ``maskadd - d_norms`` vector: two epilogue passes collapse into one,
    bitwise-identical to the unfused order because adding exact 0 is a
    no-op, ``0 - x`` is exact negation, and -inf absorbs every finite
    add.

    l2sq scores here are AFFINITIES, not full scores: the exact path's
    ``-|q|^2`` term is a per-query constant that cannot change within-query
    ranking, so the shortlist builder omits it (the same convention the
    coarse probe uses) and saves a pass per block. The exact rescore
    epilogue puts the full metric back."""
    dot = int8_dot(q_codes, d_codes)
    if metric == "l2sq":
        dot *= (2.0 * q_scales)[:, None] * d_row_scales[None, :]
        if negnorm is not None:
            dot += negnorm[None, :]
        else:
            dot -= d_norms[None, :]
            if maskadd is not None:
                dot += maskadd[None, :]
        return dot
    if metric == "cos":
        dot *= q_scales[:, None] * d_row_scales[None, :]
        dot /= np.maximum(
            np.sqrt(qn)[:, None] * np.sqrt(d_norms)[None, :], 1e-30
        )
    else:  # ip
        dot *= q_scales[:, None] * d_row_scales[None, :]
    if maskadd is not None:
        dot += maskadd[None, :]
    return dot


# ---------------------------------------------------------------------------
# exact fp32 epilogues (host) — THE pinned rescore contract
# ---------------------------------------------------------------------------


def host_metric_scores(
    q: np.ndarray, vecs: np.ndarray, norms: np.ndarray, qn: np.ndarray, metric: str
) -> np.ndarray:
    """The exact fp32 cluster-block scores ``(group_q, rows)`` — the ONE
    host metric epilogue shared by ``knn_ivf._search_numpy`` and the tiered
    store's host path (factored here so the quant rescore and the fp32
    scorers can never drift apart)."""
    s = q @ vecs.T
    if metric == "l2sq":
        s = 2.0 * s - norms[None, :] - qn[:, None]
    elif metric == "cos":
        s = s / np.maximum(np.sqrt(qn)[:, None] * np.sqrt(norms)[None, :], 1e-30)
    return s


def rescore_pairs(
    q_rows: np.ndarray, vecs: np.ndarray, norms: np.ndarray, qn_rows: np.ndarray,
    metric: str,
) -> np.ndarray:
    """THE exact rescore epilogue: fp32 scores of (query, document) PAIRS
    (one score per row of the stacked inputs). The tiered store computes its
    returned scores through this function and nothing else; the bench/test
    honesty key recomputes it over the returned (query, slot) pairs from the
    fp32 source rows — bitwise equality is the contract, so a stale
    sidecar, a wrong gather or an approximate score leaking into the output
    is a byte diff, not a recall anecdote."""
    dot = np.einsum(
        "ij,ij->i", q_rows.astype(np.float32), vecs.astype(np.float32)
    )
    if metric == "l2sq":
        return (2.0 * dot - norms - qn_rows).astype(np.float32)
    if metric == "cos":
        return (
            dot / np.maximum(np.sqrt(qn_rows) * np.sqrt(norms), 1e-30)
        ).astype(np.float32)
    return dot.astype(np.float32)


# ---------------------------------------------------------------------------
# device kernels (non-CPU backends; pow2-bucketed, registered in
# kernel_cache_sizes() beside tiered_assign / tiered_score)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("metric",))
def quant_score_block_kernel(
    qvecs: jax.Array,      # (cap, dim) int8 codes — the hot mirror payload
    scales: jax.Array,     # (cap,) f32 per-row (page-broadcast) scales
    norms: jax.Array,      # (cap,) f32 exact norms
    mask: jax.Array,       # (cap,) additive 0/-inf validity mask
    q_codes: jax.Array,    # (q_pad, dim) int8 query codes
    q_scales: jax.Array,   # (q_pad,) f32 query scales
    qn: jax.Array,         # (q_pad,) f32 exact query norms
    metric: str,
) -> jax.Array:
    """Score one hot cluster block from int8 codes on device: the int8 dot
    accumulates in f32 (exact integers for dim <= 1024 — same invariance
    argument as the host path, so device/host parity is arithmetic, not
    luck), then the shared metric epilogue shape. Block capacities and query
    batches are pow2 so the jit cache stays O(log).

    The l2sq branch mirrors :func:`approx_scores` operation-for-operation —
    2x folded into the query scales (exact pow2 multiply), the per-query
    ``-|q|^2`` shift omitted (rank-invariant for the shortlist), validity
    mask and ``-|d|^2`` fused into one add — so the first-use parity probe
    holds by the same bitwise arguments the host path relies on."""
    dotq = jnp.dot(
        q_codes.astype(jnp.float32), qvecs.astype(jnp.float32).T,
        preferred_element_type=jnp.float32,
    )
    if metric == "l2sq":
        dot = dotq * ((2.0 * q_scales)[:, None] * scales[None, :])
        return dot + (mask - norms)[None, :]
    dot = dotq * (q_scales[:, None] * scales[None, :])
    if metric == "cos":
        scores = dot / jnp.maximum(
            jnp.sqrt(qn)[:, None] * jnp.sqrt(norms)[None, :], 1e-30
        )
    else:  # ip
        scores = dot
    return scores + mask[None, :]


@jax.jit
def quant_probe_kernel(
    qcents: jax.Array,     # (C_pad, dim) int8 centroid codes
    cscales: jax.Array,    # (C_pad,) f32 per-centroid scales
    cn: jax.Array,         # (C_pad,) f32 exact |c|^2 (+inf on pad rows)
    q_codes: jax.Array,    # (q_pad, dim) int8 query codes
    q_scales: jax.Array,   # (q_pad,) f32 query scales
) -> jax.Array:
    """Coarse-probe affinity ``2 q·c - |c|^2`` from int8 codes (l2sq-order
    affinity, the same ranking the fp32 coarse probe uses for every metric).
    Centroid count pads to pow2 with ``cn = +inf`` rows (affinity -inf, never
    probed) so the jit cache is O(log^2) over (C, q) buckets."""
    dot = jnp.dot(
        q_codes.astype(jnp.float32), qcents.astype(jnp.float32).T,
        preferred_element_type=jnp.float32,
    ) * (q_scales[:, None] * cscales[None, :])
    return 2.0 * dot - cn[None, :]


def coarse_affinity(
    q_codes: np.ndarray, q_scales: np.ndarray, qcents: np.ndarray,
    cscales: np.ndarray, cn: np.ndarray,
) -> np.ndarray:
    """Host twin of :func:`quant_probe_kernel` (CPU backends skip the jit
    dispatch; the device kernel parity test pins the two together)."""
    dot = int8_dot(q_codes, qcents) * (q_scales[:, None] * cscales[None, :])
    return 2.0 * dot - cn[None, :]
