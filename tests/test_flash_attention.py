"""The causal flash-attention kernels (``kernels/flash_attention.py``) in
interpret mode, against the jnp chunked path of ``models/layers`` and a
float32 ``highest`` softmax: the output, the log-sum-exp and the three
gradients, at latent attention's widths and at grouped-query widths,
over sequences of four blocks (so that skipped, diagonal and full tiles
all occur); the bfloat16 MXU pass; and which calls of
``layers.attention`` take the kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref
from repro.models import layers

L = 512
CASES = {
    # name: (Hq, Hkv, q/k width, v width, block_q, block_kv)
    "mla": (4, 4, 192, 128, 128, 128),
    "gqa": (4, 2, 128, 128, 128, 128),
    "gqa_tall_kv": (4, 2, 128, 128, 128, 256),
    "gqa_tall_q": (4, 2, 128, 128, 256, 128),
}


def _inputs(hq, hkv, dqk, dv, seed=0, seq=L, batch=2):
    r = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(r.standard_normal(shape), jnp.float32)

    return (normal(batch, seq, hq, dqk), normal(batch, seq, hkv, dqk),
            normal(batch, seq, hkv, dv), normal(batch, seq, hq, dv))


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _chunked(q, k, v, scale):
    return layers.chunked_attention(
        q, k, v, causal=True, window=None, softcap=None, q_offset=0,
        kv_valid=None, q_chunk=128, scale=scale)


def _value_and_grads(fn, q, k, v, w):
    out = fn(q, k, v)
    grads = jax.grad(lambda *a: jnp.sum(fn(*a) * w), (0, 1, 2))(q, k, v)
    return (out, *grads)


@pytest.mark.parametrize("case", sorted(CASES))
def test_matches_chunked_path_and_reference(case):
    """At float32 on the MXU, the kernel's o, dq, dk and dv agree with
    the jnp chunked path (float32 on the CPU) and with a float32
    ``highest`` softmax over the whole score matrix, to float32
    rounding."""
    hq, hkv, dqk, dv, bq, bk = CASES[case]
    q, k, v, w = _inputs(hq, hkv, dqk, dv)
    scale = dqk**-0.5

    def kernel(*qkv):
        return fa.flash_attention(*qkv, scale=scale, block_q=bq,
                                  block_kv=bk, mxu_dtype=jnp.float32,
                                  interpret=True)

    got = _value_and_grads(kernel, q, k, v, w)
    chunked = _value_and_grads(lambda *a: _chunked(*a, scale), q, k, v, w)
    with jax.default_matmul_precision("highest"):
        want = _value_and_grads(
            lambda *a: ref.causal_attention_ref(*a, scale), q, k, v, w)
    for name, g, c, r in zip(("o", "dq", "dk", "dv"), got, chunked, want):
        assert _rel(g, c) < 1e-5, (name, _rel(g, c))
        assert _rel(g, r) < 1e-5, (name, _rel(g, r))


@pytest.mark.parametrize("case", ["mla", "gqa_tall_q"])
def test_log_sum_exp(case):
    """The forward kernel's per-row log-sum-exp, the backward's only
    saved statistic, against the masked scores' in float64."""
    hq, hkv, dqk, dv, bq, bk = CASES[case]
    q, k, v, _ = _inputs(hq, hkv, dqk, dv, seed=1)
    scale = dqk**-0.5

    def heads_first(x):
        return jnp.swapaxes(x, 1, 2).reshape(-1, L, x.shape[-1])

    _, lse = fa._fwd(heads_first(q), heads_first(k), heads_first(v),
                     scale=scale, block_q=bq, block_kv=bk,
                     mxu_dtype=jnp.float32, interpret=True)
    q64, k64 = np.asarray(q, np.float64), np.asarray(k, np.float64)
    k64 = np.repeat(k64, hq // hkv, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", q64, k64) * scale
    s = np.where(np.tril(np.ones((L, L), bool)), s, -np.inf)
    m = s.max(-1, keepdims=True)
    want = (m + np.log(np.exp(s - m).sum(-1, keepdims=True))).reshape(-1, L)
    np.testing.assert_allclose(np.asarray(lse)[..., 0], want, rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("case", ["mla", "gqa"])
def test_bfloat16_mxu_pass(case):
    """At bfloat16 every tile enters the MXU rounded once: o and the
    gradients sit ~3e-3 from the float32 reference, far above float32
    rounding and far below what a wrong mask or block would give."""
    hq, hkv, dqk, dv, bq, bk = CASES[case]
    q, k, v, w = _inputs(hq, hkv, dqk, dv, seed=2)
    scale = dqk**-0.5

    def kernel(*qkv):
        return fa.flash_attention(*qkv, scale=scale, block_q=bq,
                                  block_kv=bk, mxu_dtype=jnp.bfloat16,
                                  interpret=True)

    got = _value_and_grads(kernel, q, k, v, w)
    with jax.default_matmul_precision("highest"):
        want = _value_and_grads(
            lambda *a: ref.causal_attention_ref(*a, scale), q, k, v, w)
    for name, g, r in zip(("o", "dq", "dk", "dv"), got, want):
        assert 1e-4 < _rel(g, r) < 1e-2, (name, _rel(g, r))


def test_mxu_dtype_follows_the_precision():
    """The dispatch feeds the MXU one bfloat16 pass on a TPU at XLA's
    default precision, float32 where the precision asks for more and in
    interpret mode."""
    assert ops._mxu_dtype(interpret=False) == jnp.bfloat16
    assert ops._mxu_dtype(interpret=True) == jnp.float32
    with jax.default_matmul_precision("highest"):
        assert ops._mxu_dtype(interpret=False) == jnp.float32


LONG = 2 * max(fa.BLOCK_Q, fa.BLOCK_KV)


@pytest.mark.parametrize("case", [
    "short", "window", "softcap", "kv_valid", "q_offset", "bidirectional",
])
def test_bypass_takes_the_chunked_path(case, monkeypatch):
    """A call the kernel does not cover runs the chunked jnp path and
    gives its former output: a sequence under two blocks (SASRec's 200),
    a sliding window or a softcap (gemma-2), a key-validity mask or a
    query offset (decode and prefill), or bidirectional attention
    (BERT4Rec)."""
    seq = 200 if case == "short" else LONG
    q, k, v, _ = _inputs(2, 1, 16, 16, seed=3, seq=seq, batch=1)
    kw = dict(causal=True, window=None, softcap=None, q_offset=0,
              kv_valid=None, q_chunk=256, scale=0.25)
    if case == "window":
        kw["window"] = 64
    elif case == "softcap":
        kw["softcap"] = 5.0
    elif case == "kv_valid":
        kw["kv_valid"] = jnp.arange(seq)[None, :] < seq - 3
    elif case == "q_offset":
        kw["q_offset"] = 8
    elif case == "bidirectional":
        kw["causal"] = False
    if case == "short":
        kw["q_chunk"] = 1024

    def no_kernel(*a, **k):
        raise AssertionError("the flash kernel was called")

    monkeypatch.setattr(ops, "causal_attention", no_kernel)
    got = layers.attention(q, k, v, **kw)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(layers.chunked_attention(
                                      q, k, v, **kw)))


def test_long_causal_call_takes_the_kernel(monkeypatch):
    """The call the bypass cases vary takes the kernel."""
    q, k, v, _ = _inputs(2, 1, 16, 16, seed=3, seq=LONG, batch=1)
    calls = []
    real = ops.causal_attention

    def spy(*a, **k):
        calls.append(a[0].shape)
        return real(*a, **k)

    monkeypatch.setattr(ops, "causal_attention", spy)
    got = layers.attention(q, k, v, causal=True, q_chunk=256, scale=0.25)
    assert calls == [q.shape]
    want = layers.chunked_attention(
        q, k, v, causal=True, window=None, softcap=None, q_offset=0,
        kv_valid=None, q_chunk=256, scale=0.25)
    assert _rel(got, want) < 1e-5
