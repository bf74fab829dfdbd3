"""Per-arch smoke tests (task deliverable f): every assigned architecture
instantiates its REDUCED config and runs one forward/train step on CPU,
asserting output shapes and all-finite values. Plus LM decode-vs-forward
consistency and MoE dispatch invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, list_archs
from repro.launch.mesh import make_host_mesh
from repro.launch.train import SmokeShape, _init_params, _make_step

ALL_ARCHS = list(list_archs())


def test_registry_complete():
    # 10 assigned + sasrec-sce (paper's own) + moonlight-16b-a3b (the
    # LM trained through the SCE head on one chip)
    assert len(ALL_ARCHS) == 12
    for name in [
        "deepseek-coder-33b", "yi-6b", "gemma2-2b", "kimi-k2-1t-a32b",
        "granite-moe-3b-a800m", "schnet", "dcn-v2", "dlrm-rm2",
        "bert4rec", "xdeepfm", "sasrec-sce", "moonlight-16b-a3b",
    ]:
        assert name in ALL_ARCHS


def test_full_configs_match_assignment():
    """The FULL configs carry the exact published numbers."""
    c = get_arch("deepseek-coder-33b").make_config("train_4k")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (62, 7168, 56, 8, 19200, 32256)
    c = get_arch("yi-6b").make_config("train_4k")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (32, 4096, 32, 4, 11008, 64000)
    c = get_arch("gemma2-2b").make_config("train_4k")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (26, 2304, 8, 4, 9216, 256000)
    assert c.attn_pattern == ("local", "global") and c.final_softcap
    c = get_arch("kimi-k2-1t-a32b").make_config("train_4k")
    assert (c.n_layers, c.d_model, c.n_heads, c.moe.n_experts,
            c.moe.top_k, c.vocab) == (61, 7168, 64, 384, 8, 163840)
    assert 0.9e12 < c.param_count() < 1.2e12  # ~1T total
    assert 25e9 < c.active_param_count() < 40e9  # ~32B active
    c = get_arch("granite-moe-3b-a800m").make_config("train_4k")
    assert (c.moe.n_experts, c.moe.top_k, c.vocab) == (40, 8, 49155)
    assert 2.5e9 < c.param_count() < 3.5e9
    c = get_arch("schnet").make_config("molecule")
    assert (c.n_interactions, c.d_hidden, c.n_rbf, c.cutoff) == (3, 64, 300, 10.0)
    c = get_arch("dcn-v2").make_config()
    assert (c.n_dense, len(c.vocab_sizes), c.embed_dim,
            c.n_cross_layers) == (13, 26, 16, 3)
    c = get_arch("dlrm-rm2").make_config()
    assert (c.embed_dim, c.bot_mlp, c.top_mlp) == (
        64, (512, 256, 64), (512, 512, 256, 1))
    c = get_arch("bert4rec").make_config()
    assert (c.d_model, c.n_layers, c.n_heads, c.max_len) == (64, 2, 2, 200)
    c = get_arch("xdeepfm").make_config()
    assert (len(c.vocab_sizes), c.embed_dim, c.cin_layers) == (
        39, 10, (200, 200, 200))


def test_40_cell_grid_accounting():
    """10 assigned archs × 4 shapes = 40 cells; documented skips only for
    full-attention long_500k (DESIGN.md §5)."""
    cells = skips = 0
    for name in ALL_ARCHS:
        if name in ("sasrec-sce", "moonlight-16b-a3b"):
            continue  # the beyond-assignment archs
        for shape in get_arch(name).shapes:
            cells += 1
            if shape.skip is not None:
                skips += 1
                assert shape.name == "long_500k"
    assert cells == 40 and skips == 4


@pytest.mark.slow  # real train steps per arch, ~1-3 min each on CPU
@pytest.mark.parametrize("arch_name", ALL_ARCHS)
def test_arch_smoke_train_step(arch_name):
    """One real train step on the reduced config: shapes + no NaNs."""
    from repro.launch.train import train

    out = train(arch_name, steps=2, batch=4, seq_len=16)
    assert out["steps"] == 2
    assert np.isfinite(out["final_loss"])


def test_lm_decode_matches_forward(key):
    """Prefill + decode_step must reproduce teacher-forced forward logits
    (gemma2 smoke config: exercises local/global + rolling cache)."""
    from repro.models import transformer as tf

    cfg = get_arch("gemma2-2b").make_smoke_config()
    params = tf.init_params(key, cfg)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (2, 24), 1,
                                cfg.vocab)

    hidden, _ = tf.forward(params, cfg, tokens)
    full_logits = tf.logits_from_hidden(params, cfg, hidden)

    cache = tf.init_cache(cfg, 2, 24)
    logits_steps = []
    for pos in range(24):
        logits, cache = tf.decode_step(
            params, cfg, cache, tokens[:, pos : pos + 1], pos
        )
        logits_steps.append(logits[:, 0])
    dec = jnp.stack(logits_steps, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec), np.asarray(full_logits), rtol=2e-3, atol=2e-3
    )


def test_lm_prefill_then_decode(key):
    """prefill() cache must continue exactly like step-by-step decode."""
    from repro.models import transformer as tf

    cfg = get_arch("yi-6b").make_smoke_config()
    params = tf.init_params(key, cfg)
    tokens = jax.random.randint(jax.random.fold_in(key, 3), (1, 16), 1,
                                cfg.vocab)
    prompt, nxt = tokens[:, :12], tokens[:, 12:13]

    hidden, cache = tf.prefill(params, cfg, prompt, cache_len=16)
    logits_a, _ = tf.decode_step(params, cfg, cache, nxt, 12)

    cache2 = tf.init_cache(cfg, 1, 16)
    for pos in range(12):
        _, cache2 = tf.decode_step(
            params, cfg, cache2, prompt[:, pos : pos + 1], pos
        )
    logits_b, _ = tf.decode_step(params, cfg, cache2, nxt, 12)
    np.testing.assert_allclose(
        np.asarray(logits_a), np.asarray(logits_b), rtol=2e-3, atol=2e-3
    )


def test_moe_dispatch_no_drops_matches_dense(key):
    """Token-choice dispatch must equal the dense (every-expert)
    computation weighted by router probs (top_k = E ⇒ every expert)."""
    from repro.models import moe as moe_lib

    cfg = moe_lib.MoEConfig(n_experts=4, top_k=4, d_ff=8)
    d = 6
    params = moe_lib.init_moe(key, d, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 5, d))
    out, aux, routing = moe_lib.apply_moe(params, x, cfg)

    # dense reference: softmax over ALL experts (top_k = E ⇒ same)
    logits = jnp.einsum("bld,de->ble", x, params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate = jax.nn.silu(jnp.einsum("bld,edf->blef", x, params["w_gate"]))
    up = jnp.einsum("bld,edf->blef", x, params["w_up"])
    y_e = jnp.einsum("blef,efd->bled", gate * up, params["w_down"])
    want = jnp.einsum("bled,ble->bld", y_e, probs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert np.isfinite(float(aux))
    assert int(routing["load"].sum()) == 2 * 5 * 4


def test_schnet_permutation_invariance(key):
    """Graph-level energy must be invariant to node relabeling."""
    from repro.configs.schnet import make_smoke_config
    from repro.models import schnet

    cfg = make_smoke_config()
    params = schnet.init_params(key, cfg)
    n, e = 10, 30
    feats = jax.random.normal(jax.random.fold_in(key, 1), (n, cfg.d_feat))
    pos = jax.random.uniform(jax.random.fold_in(key, 2), (n, 3)) * 4
    src = jax.random.randint(jax.random.fold_in(key, 3), (e,), 0, n)
    dst = jax.random.randint(jax.random.fold_in(key, 4), (e,), 0, n)
    ei = jnp.stack([src, dst])

    e1, _ = schnet.forward(params, cfg, feats, pos, ei)

    perm = np.random.permutation(n)
    inv = np.argsort(perm)
    e2, _ = schnet.forward(
        params, cfg, feats[perm], pos[perm], jnp.asarray(inv)[ei]
    )
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), rtol=1e-4)


def test_recsys_retrieval_chunked_equals_direct(key):
    """retrieval_scores (lax.map chunks) == direct forward substitution."""
    from repro.configs import get_arch
    from repro.models import recsys

    cfg = get_arch("dcn-v2").make_smoke_config()
    params = recsys.init_dcn_v2(key, cfg)
    dense = jax.random.normal(jax.random.fold_in(key, 1), (1, cfg.n_dense))
    sparse = jax.random.randint(
        jax.random.fold_in(key, 2), (1, len(cfg.vocab_sizes), 1), 0, 10
    )
    cands = jnp.arange(37)
    scores = recsys.retrieval_scores(
        recsys.dcn_v2_forward, params, cfg, dense, sparse, cands, chunk=16
    )
    direct = []
    for c in range(37):
        s = sparse.at[:, 0, 0].set(c)
        direct.append(recsys.dcn_v2_forward(params, cfg, dense, s)[0])
    np.testing.assert_allclose(
        np.asarray(scores), np.asarray(jnp.stack(direct)), rtol=1e-5,
        atol=1e-6,
    )


def test_bert4rec_cloze_mask(key):
    from repro.configs import get_arch
    from repro.models import bert4rec as b4r

    cfg = get_arch("bert4rec").make_smoke_config()
    tokens = jax.random.randint(key, (8, cfg.max_len), 1, cfg.n_items)
    tokens = tokens.at[:, :5].set(0)  # padding
    masked, is_masked = b4r.apply_cloze_mask(key, tokens, cfg, 0.3)
    assert not bool(jnp.any(is_masked[:, :5]))  # never mask padding
    assert bool(jnp.any(is_masked))
    np.testing.assert_array_equal(
        np.asarray(masked[is_masked]),
        np.full(int(is_masked.sum()), b4r.mask_token_id(cfg)),
    )
