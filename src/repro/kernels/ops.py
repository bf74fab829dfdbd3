"""Jit'd public wrappers around the Pallas kernels.

On CPU (this container) the kernels execute in ``interpret=True`` mode —
the kernel body runs as traced JAX ops for bit-accurate validation. On a
real TPU backend they compile to Mosaic.

Every production dispatch here is guarded (``kernels/guard``, policy
``REPRO_GUARD`` ∈ {off, warn, strict}, default warn):

  * block configs run through ``guard.checked_blocks`` — analytic
    legality + VMEM preflight with auto-repair, or a structured
    ``KernelPreflightError`` instead of a deep Mosaic failure;
  * the kernel branch consults ``guard.kernel_enabled`` — the memoized
    per-(backend, kernel) conformance-canary verdict. On TPU a failed
    verdict raises ``KernelConformanceError``; elsewhere (interpret
    mode) a failing kernel DEGRADES to the chunked ``ref.py`` path with
    a loud warning under policy ``warn``.

A traced ``id_offset`` (``axis_index * C_local`` inside ``shard_map``)
runs the kernel like a static one: the kernels select local ids and
shift the real ones by the offset afterwards.

``REPRO_FORCE_INTERPRET=1`` forces interpret mode on any backend (the
kernel-body debugging escape hatch).
"""
from __future__ import annotations

import functools
import os
import warnings

import jax
import jax.numpy as jnp

from repro.kernels import eval_fused as _eval_fused
from repro.kernels import eval_topk as _eval_topk
from repro.kernels import flash_attention as _flash_attention
from repro.kernels import fused_ce as _fused_ce
from repro.kernels import guard as _guard
from repro.kernels import linear_sce as _linear_sce
from repro.kernels import mips_topk as _mips_topk
from repro.kernels import ref as _ref
from repro.kernels import sce_bucket as _sce_bucket
from repro.kernels import sce_prefetch as _sce_prefetch

_TWO_PASS_DEPRECATION = (
    "the two-pass eval scorer ({name}) is deprecated as a production "
    "entry point — it streams the catalog matmul once per pass where "
    "kernels.ops.eval_fused streams it once TOTAL. It is retained only "
    "as the oracle for the fused path's differential tests."
)

_gpu_interpret_warned = False


@functools.lru_cache(maxsize=1)
def _default_backend() -> str:
    """Memoized backend probe — ``jax.default_backend()`` initializes
    the platform on first call; every dispatch afterwards is a cached
    string."""
    return jax.default_backend()


def _interpret_for_backend(backend: str) -> bool:
    """Interpret-mode decision for a named backend: Mosaic on TPU,
    interpret everywhere else — with the GPU case explicit (no
    Mosaic-GPU lowering is wired up; falling to interpret there is
    loudly announced once rather than silently assumed)."""
    global _gpu_interpret_warned
    if backend == "tpu":
        return False
    if backend == "gpu" and not _gpu_interpret_warned:
        _gpu_interpret_warned = True
        warnings.warn(
            "[kernels.ops] GPU backend detected but no Mosaic-GPU "
            "lowering is wired up — Pallas kernels run in interpret "
            "mode (exact, SLOW). Pass interpret=False explicitly once "
            "a GPU lowering lands.",
            RuntimeWarning, stacklevel=3,
        )
    return True


def _interpret_default() -> bool:
    if os.environ.get("REPRO_FORCE_INTERPRET") == "1":
        return True
    return _interpret_for_backend(_default_backend())


def _inside_shard_map(*arrays) -> bool:
    """True if any operand carries varying-manual-axes (i.e. we are being
    traced inside ``jax.shard_map``)."""
    for a in arrays:
        try:
            if jax.typeof(a).vma:
                return True
        except (AttributeError, TypeError):
            pass
    return False


def _mxu_dtype(interpret: bool):
    """The flash kernels' MXU operand dtype: on a TPU, one bfloat16
    pass where the process's matmul precision is XLA's default (what the
    jnp einsums beside the kernel get), float32 at HIGHEST where it asks
    for more; in interpret mode float32, as XLA's CPU default is."""
    one_pass = jax.config.jax_default_matmul_precision in (
        None, "default", "bfloat16", "fastest")
    return jnp.bfloat16 if one_pass and not interpret else jnp.float32


def causal_attention(
    q,
    k,
    v,
    *,
    scale: float,
    interpret: bool | None = None,
):
    """Causal self-attention over whole sequences through the flash
    kernels: ``q (B, L, Hq, dqk)``, ``k (B, L, Hkv, dqk)``, ``v (B, L,
    Hkv, dv)`` → ``(B, L, Hq, dv)``; ``L`` a multiple of both of the
    kernel's blocks. See kernels/flash_attention.py. Inside
    ``shard_map`` in interpret mode the float32 reference runs
    instead."""
    if interpret is None:
        interpret = _interpret_default()
    if interpret and _inside_shard_map(q, k, v):
        return _ref.causal_attention_ref(q, k, v, scale)
    block_q, block_kv = _guard.checked_blocks(
        "flash_attention", rows=q.shape[1], cols=k.shape[1],
        d=q.shape[-1], block_rows=_flash_attention.BLOCK_Q,
        block_cols=_flash_attention.BLOCK_KV, dtype=q.dtype,
    )
    if not _guard.kernel_enabled("flash_attention", interpret=interpret):
        return _ref.causal_attention_ref(q, k, v, scale)
    return _flash_attention.flash_attention(
        q, k, v, scale=scale, block_q=block_q, block_kv=block_kv,
        mxu_dtype=_mxu_dtype(interpret), interpret=interpret,
    )


def sce_bucket_loss(
    x_b,
    y_b,
    tgt_b,
    cand_ids,
    pos_logit,
    *,
    block_bx: int = 128,
    block_by: int = 256,
    interpret: bool | None = None,
    logit_softcap: float | None = None,
):
    """Fused in-bucket SCE losses (n_b, b_x). See kernels/sce_bucket.py.
    ``logit_softcap`` caps negatives inside the tile; ``pos_logit`` must
    arrive already capped."""
    if interpret is None:
        interpret = _interpret_default()
    if interpret and _inside_shard_map(x_b, y_b, pos_logit):
        # Pallas interpret-mode (hlo_interpreter) cannot yet run inside
        # shard_map with VMA checking (jax 0.8 limitation); the pure-jnp
        # oracle is numerically identical. On TPU the kernel runs as-is.
        return _ref.sce_bucket_loss_ref(
            x_b, y_b, tgt_b, cand_ids, pos_logit, logit_softcap
        )
    block_bx, block_by = _guard.checked_blocks(
        "sce_bucket", rows=x_b.shape[1], cols=y_b.shape[1],
        d=x_b.shape[-1], block_rows=block_bx, block_cols=block_by,
        dtype=x_b.dtype,
    )
    if not _guard.kernel_enabled("sce_bucket", interpret=interpret):
        return _ref.sce_bucket_loss_ref(
            x_b, y_b, tgt_b, cand_ids, pos_logit, logit_softcap
        )
    return _sce_bucket.sce_bucket_loss(
        x_b, y_b, tgt_b, cand_ids, pos_logit, block_bx, block_by, interpret,
        logit_softcap,
    )


def sce_bucket_plse(
    x_b,
    y_b,
    tgt_b,
    cand_ids,
    *,
    block_bx: int = 128,
    block_by: int = 256,
    interpret: bool | None = None,
    logit_softcap: float | None = None,
):
    """Partial in-bucket logsumexp (union-mode building block), (n_b, b_x)."""
    if interpret is None:
        interpret = _interpret_default()
    if interpret and _inside_shard_map(x_b, y_b):
        return _ref.sce_bucket_plse_ref(
            x_b, y_b, tgt_b, cand_ids, logit_softcap
        )
    block_bx, block_by = _guard.checked_blocks(
        "sce_bucket", rows=x_b.shape[1], cols=y_b.shape[1],
        d=x_b.shape[-1], block_rows=block_bx, block_cols=block_by,
        dtype=x_b.dtype,
    )
    if not _guard.kernel_enabled("sce_bucket", interpret=interpret):
        return _ref.sce_bucket_plse_ref(
            x_b, y_b, tgt_b, cand_ids, logit_softcap
        )
    return _sce_bucket.sce_bucket_plse(
        x_b, y_b, tgt_b, cand_ids, block_bx, block_by, interpret,
        logit_softcap,
    )


def mips_topk(
    q,
    y,
    k: int,
    *,
    valid=None,
    block_q: int = 128,
    block_c: int = 512,
    id_offset: int = 0,
    interpret: bool | None = None,
):
    """Streaming per-row MIPS top-k of ``q @ yᵀ`` →
    ``(vals (n_q, k), ids (n_q, k))`` without the ``(n_q, C)`` score
    matrix. See kernels/mips_topk.py; inside ``shard_map`` in interpret
    mode the chunked pure-jnp reference runs instead — same outputs and
    ``lax.top_k`` tie rule."""
    if interpret is None:
        interpret = _interpret_default()
    if interpret and _inside_shard_map(q, y):
        return _ref.mips_topk_ref(
            q, y, k, valid=valid, chunk=block_c, id_offset=id_offset
        )
    block_q, block_c = _guard.checked_blocks(
        "mips_topk", rows=q.shape[0], cols=y.shape[0], d=q.shape[-1],
        block_rows=block_q, block_cols=block_c, dtype=q.dtype, k=k,
    )
    if not _guard.kernel_enabled("mips_topk", interpret=interpret):
        return _ref.mips_topk_ref(
            q, y, k, valid=valid, chunk=block_c, id_offset=id_offset
        )
    return _mips_topk.mips_topk(
        q, y, k,
        valid=valid, block_q=block_q, block_c=block_c,
        id_offset=id_offset, interpret=interpret,
    )


def sce_gather_loss(
    x_b,
    y,
    idx_y,
    tgt_b,
    cand_ids,
    pos_logit,
    *,
    block_bx: int = 128,
    block_by: int = 256,
    interpret: bool | None = None,
    logit_softcap: float | None = None,
):
    """Fused scalar-prefetch in-bucket SCE losses (n_b, b_x): candidate
    rows are gathered from the full ``y`` (C, d) table on the fly via
    ``idx_y`` — the ``(n_b, b_y, d)`` HBM candidate tensor and its VJP
    scatter never exist. See kernels/sce_prefetch.py. Inside
    ``shard_map`` on non-TPU backends the take + pure-jnp oracle runs
    instead (numerically identical; the gather materializes there).
    ``logit_softcap`` caps negatives inside the tile; ``pos_logit``
    must arrive already capped."""
    if interpret is None:
        interpret = _interpret_default()

    def _ref_path():
        y_b = jnp.take(y, jnp.clip(idx_y, 0, y.shape[0] - 1), axis=0)
        return _ref.sce_bucket_loss_ref(
            x_b, y_b, tgt_b, cand_ids, pos_logit, logit_softcap
        )

    if interpret and _inside_shard_map(x_b, y, pos_logit):
        return _ref_path()
    block_bx, block_by = _guard.checked_blocks(
        "sce_gather", rows=x_b.shape[1], cols=idx_y.shape[1],
        d=x_b.shape[-1], block_rows=block_bx, block_cols=block_by,
        dtype=x_b.dtype,
    )
    if not _guard.kernel_enabled("sce_gather", interpret=interpret):
        return _ref_path()
    return _sce_prefetch.sce_gather_loss(
        x_b, y, idx_y, tgt_b, cand_ids, pos_logit,
        block_bx, block_by, interpret, logit_softcap,
    )


def sce_gather_plse(
    x_b,
    y,
    idx_y,
    tgt_b,
    cand_ids,
    *,
    block_bx: int = 128,
    block_by: int = 256,
    interpret: bool | None = None,
    logit_softcap: float | None = None,
):
    """Scalar-prefetch partial in-bucket logsumexp (n_b, b_x) — the
    distributed-merge building block with on-the-fly candidate gather
    (candidates with negative ``cand_ids`` are masked: padding or
    other-shard-owned rows). Same shard_map fallback as
    :func:`sce_gather_loss`."""
    if interpret is None:
        interpret = _interpret_default()

    def _ref_path():
        y_b = jnp.take(y, jnp.clip(idx_y, 0, y.shape[0] - 1), axis=0)
        return _ref.sce_bucket_plse_ref(
            x_b, y_b, tgt_b, cand_ids, logit_softcap
        )

    if interpret and _inside_shard_map(x_b, y):
        return _ref_path()
    block_bx, block_by = _guard.checked_blocks(
        "sce_gather", rows=x_b.shape[1], cols=idx_y.shape[1],
        d=x_b.shape[-1], block_rows=block_bx, block_cols=block_by,
        dtype=x_b.dtype,
    )
    if not _guard.kernel_enabled("sce_gather", interpret=interpret):
        return _ref_path()
    return _sce_prefetch.sce_gather_plse(
        x_b, y, idx_y, tgt_b, cand_ids, block_bx, block_by, interpret,
        logit_softcap,
    )


def fused_lse(
    x, y, *, block_n: int = 256, block_c: int = 512, interpret: bool | None = None
):
    """Streaming full-catalog logsumexp (N,). See kernels/fused_ce.py."""
    if interpret is None:
        interpret = _interpret_default()
    if interpret and _inside_shard_map(x, y):
        return _ref.fused_lse_ref(x, y)
    block_n, block_c = _guard.checked_blocks(
        "fused_ce", rows=x.shape[0], cols=y.shape[0], d=x.shape[-1],
        block_rows=block_n, block_cols=block_c, dtype=x.dtype,
    )
    if not _guard.kernel_enabled("fused_ce", interpret=interpret):
        return _ref.fused_lse_ref(x, y)
    return _fused_ce.fused_lse(x, y, block_n, block_c, interpret)


def fused_ce_loss(
    x,
    y,
    targets,
    *,
    block_n: int = 256,
    block_c: int = 512,
    interpret: bool | None = None,
):
    """Streaming per-position full-CE loss (N,)."""
    if interpret is None:
        interpret = _interpret_default()
    if interpret and _inside_shard_map(x, y):
        return _ref.fused_ce_loss_ref(x, y, targets)
    block_n, block_c = _guard.checked_blocks(
        "fused_ce", rows=x.shape[0], cols=y.shape[0], d=x.shape[-1],
        block_rows=block_n, block_cols=block_c, dtype=x.dtype,
    )
    if not _guard.kernel_enabled("fused_ce", interpret=interpret):
        return _ref.fused_ce_loss_ref(x, y, targets)
    return _fused_ce.fused_ce_loss(x, y, targets, block_n, block_c, interpret)


def linear_ce_loss(
    x,
    w,
    targets,
    *,
    logit_softcap: float | None = None,
    block_n: int = 256,
    block_c: int = 512,
    interpret: bool | None = None,
):
    """Fused linear cross-entropy: per-position full-vocab CE loss (N,)
    straight from ``(N, d)`` hidden states + the ``(V, d)`` head table —
    the ``(N, V)`` logit matrix never exists, forward OR backward (dX
    and dW stream the same tiles; the positive is extracted inside the
    sweep, so ``logit_softcap`` caps it consistently with the
    negatives). See kernels/linear_sce.py; inside ``shard_map`` on
    non-TPU backends the chunked pure-jnp reference runs instead."""
    if interpret is None:
        interpret = _interpret_default()
    if interpret and _inside_shard_map(x, w):
        return _ref.linear_ce_loss_ref(
            x, w, targets, logit_softcap=logit_softcap, chunk=block_c
        )
    block_n, block_c = _guard.checked_blocks(
        "linear_sce", rows=x.shape[0], cols=w.shape[0], d=x.shape[-1],
        block_rows=block_n, block_cols=block_c, dtype=x.dtype,
    )
    if not _guard.kernel_enabled("linear_sce", interpret=interpret):
        return _ref.linear_ce_loss_ref(
            x, w, targets, logit_softcap=logit_softcap, chunk=block_c
        )
    return _linear_sce.linear_ce_loss(
        x, w, targets, logit_softcap, block_n, block_c, interpret
    )


def eval_fused(
    x,
    y,
    targets,
    k: int,
    *,
    tgt_scores=None,
    block_b: int = 128,
    block_c: int = 512,
    c_lo: int = 0,
    c_hi: int | None = None,
    id_offset: int = 0,
    logit_softcap: float | None = None,
    with_lse: bool = False,
    interpret: bool | None = None,
):
    """Fused single-sweep eval scorer: top-k + target rank counts
    (+ optional online-LSE carry) from ONE catalog matmul pass →
    ``(vals (B,k), ids (B,k), gt (B,), eq (B,), tgt (B,), m, s)``
    (``m``/``s`` None unless ``with_lse``; ``lse = m + log s``). The
    production replacement for the deprecated two-pass
    ``eval_tgt_scores`` → ``eval_topk`` chain — bit-identical ranks,
    ids, tie order and target scores at half the catalog FLOPs/traffic
    (a third, for the LM path, whose separate NLL sweep the LSE carry
    absorbs). See kernels/eval_fused.py; inside ``shard_map`` in
    interpret mode the chunked pure-jnp reference runs instead — same
    outputs and tie rule. Sharded callers precompute
    the threshold (``psum`` of per-shard :func:`eval_tgt_gather`) and
    pass it via ``tgt_scores``."""
    if interpret is None:
        interpret = _interpret_default()

    def _ref_path():
        return _ref.eval_fused_ref(
            x, y, targets, k,
            tgt_scores=tgt_scores, chunk=block_c, c_lo=c_lo, c_hi=c_hi,
            id_offset=id_offset, logit_softcap=logit_softcap,
            with_lse=with_lse,
        )

    if interpret and _inside_shard_map(x, y):
        return _ref_path()
    block_b, block_c = _guard.checked_blocks(
        "eval_fused", rows=x.shape[0], cols=y.shape[0], d=x.shape[-1],
        block_rows=block_b, block_cols=block_c, dtype=x.dtype, k=k,
    )
    if not _guard.kernel_enabled("eval_fused", interpret=interpret):
        return _ref_path()
    return _eval_fused.eval_fused(
        x, y, targets, k,
        tgt_scores=tgt_scores, block_b=block_b, block_c=block_c,
        c_lo=c_lo, c_hi=c_hi, id_offset=id_offset,
        logit_softcap=logit_softcap, with_lse=with_lse,
        interpret=interpret,
    )


def eval_tgt_gather(
    x,
    y,
    targets,
    *,
    block_b: int = 128,
    block_c: int = 512,
    id_offset: int = 0,
    interpret: bool | None = None,
):
    """Target-column scores from tile-shaped gather matmuls — bitwise
    identical to the column :func:`eval_fused`'s sweep computes (same
    gemm shape ⇒ same per-element reduction) at ``O(B·block_c·d)``
    FLOPs instead of the deprecated ``eval_tgt_scores`` full sweep.
    → (B,) f32; rows whose target falls outside ``y``'s id range
    contribute 0, so a ``psum`` over catalog shards assembles the
    exact value. Call with the SAME ``block_c`` as the sweep."""
    if interpret is None:
        interpret = _interpret_default()
    if interpret and _inside_shard_map(x, y):
        return _ref.eval_tgt_gather_ref(
            x, y, targets, chunk=block_c, id_offset=id_offset
        )
    block_b, block_c = _guard.checked_blocks(
        "eval_fused", rows=x.shape[0], cols=y.shape[0], d=x.shape[-1],
        block_rows=block_b, block_cols=block_c, dtype=x.dtype,
    )
    if not _guard.kernel_enabled("eval_fused", interpret=interpret):
        return _ref.eval_tgt_gather_ref(
            x, y, targets, chunk=block_c, id_offset=id_offset
        )
    return _eval_fused.eval_tgt_gather(
        x, y, targets,
        block_b=block_b, block_c=block_c,
        id_offset=id_offset, interpret=interpret,
    )


def eval_topk(
    x,
    y,
    tgt_scores,
    k: int,
    *,
    block_b: int = 128,
    block_c: int = 512,
    c_lo: int = 0,
    c_hi: int | None = None,
    id_offset: int = 0,
    interpret: bool | None = None,
):
    """DEPRECATED two-pass rank-and-topk (oracle only — use
    :func:`eval_fused`). Streaming full-catalog top-k + target rank
    counts → ``(vals (B,k), ids (B,k), gt (B,), eq (B,))``. See
    kernels/eval_topk.py; inside ``shard_map`` (or with a traced
    ``id_offset``) the chunked pure-jnp reference runs instead — same
    outputs and tie rule."""
    warnings.warn(
        _TWO_PASS_DEPRECATION.format(name="eval_topk"),
        DeprecationWarning, stacklevel=2,
    )
    if interpret is None:
        interpret = _interpret_default()

    def _ref_path():
        return _ref.eval_topk_ref(
            x, y, tgt_scores, k,
            chunk=block_c, c_lo=c_lo, c_hi=c_hi, id_offset=id_offset,
        )

    traced_offset = not isinstance(id_offset, int)
    if traced_offset or (interpret and _inside_shard_map(x, y)):
        return _ref_path()
    block_b, block_c = _guard.checked_blocks(
        "eval_topk", rows=x.shape[0], cols=y.shape[0], d=x.shape[-1],
        block_rows=block_b, block_cols=block_c, dtype=x.dtype, k=k,
    )
    if not _guard.kernel_enabled("eval_topk", interpret=interpret):
        return _ref_path()
    return _eval_topk.eval_topk(
        x, y, tgt_scores, k,
        block_b=block_b, block_c=block_c,
        c_lo=c_lo, c_hi=c_hi, id_offset=id_offset, interpret=interpret,
    )


def eval_tgt_scores(
    x,
    y,
    targets,
    *,
    block_b: int = 128,
    block_c: int = 512,
    id_offset: int = 0,
    interpret: bool | None = None,
):
    """DEPRECATED full-sweep target extraction (oracle only — use
    :func:`eval_tgt_gather`, or just :func:`eval_fused`). Target-column
    scores from the same streamed tile matmul ``eval_topk`` runs (call
    with the SAME ``block_c`` so the counts it feeds are
    bitwise-exact). → (B,) f32. Same shard_map / traced-offset fallback
    to the chunked reference as ``eval_topk``."""
    warnings.warn(
        _TWO_PASS_DEPRECATION.format(name="eval_tgt_scores"),
        DeprecationWarning, stacklevel=2,
    )
    if interpret is None:
        interpret = _interpret_default()
    traced_offset = not isinstance(id_offset, int)
    if traced_offset or (interpret and _inside_shard_map(x, y)):
        return _ref.eval_tgt_scores_ref(
            x, y, targets, chunk=block_c, id_offset=id_offset
        )
    block_b, block_c = _guard.checked_blocks(
        "eval_topk", rows=x.shape[0], cols=y.shape[0], d=x.shape[-1],
        block_rows=block_b, block_cols=block_c, dtype=x.dtype,
    )
    if not _guard.kernel_enabled("eval_topk", interpret=interpret):
        return _ref.eval_tgt_scores_ref(
            x, y, targets, chunk=block_c, id_offset=id_offset
        )
    return _eval_topk.eval_tgt_scores(
        x, y, targets,
        block_b=block_b, block_c=block_c,
        id_offset=id_offset, interpret=interpret,
    )
