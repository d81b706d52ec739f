"""Per-architecture smoke tests: reduced config, one forward + train step +
decode step on CPU, asserting output shapes and finiteness (no NaNs), for
every arch the LM stack builds (``model.builds``); plus full-config
closed-form parameter counts against the published model sizes, for every
arch, and against the LM stack's own count where it builds the arch."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs.params import count_params
from repro.configs.registry import ARCH_IDS, SHAPES, all_cells, applicable, get_arch
from repro.models import model

# published sizes (total params, billions) with tolerance bands
EXPECTED_B = {
    "musicgen_medium": (1.38, 0.3),  # 1.5B-class (4 codebook heads)
    "jamba_v01_52b": (52, 3),
    "qwen2_vl_7b": (7.6, 0.8),
    "xlstm_1p3b": (2.0, 0.7),  # unverified config; block-internal projections
    "granite_20b": (20, 1.5),
    "yi_6b": (6, 0.5),
    "qwen15_4b": (4, 0.4),
    "qwen3_8b": (8.2, 0.6),
    "llama4_maverick_400b": (400, 15),
    "mixtral_8x7b": (46.7, 2),
    "deepseek_v3": (671, 10),  # without the MTP module
}

ACTIVE_B = {  # active (FLOP-bearing) params for the MoE archs
    "llama4_maverick_400b": (17, 3),
    "mixtral_8x7b": (12.9, 1.5),
    "deepseek_v3": (37, 1.5),
}

# archs whose mixers and layer stack the LM stack builds
BUILT = [a for a in ARCH_IDS if model.builds(get_arch(a))]


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_param_count(arch):
    cfg = get_arch(arch)
    n = cfg.param_count() / 1e9
    want, tol = EXPECTED_B[arch]
    assert abs(n - want) <= tol, f"{arch}: {n:.2f}B vs {want}B"
    if arch in ACTIVE_B:
        na = cfg.active_param_count() / 1e9
        want_a, tol_a = ACTIVE_B[arch]
        assert abs(na - want_a) <= tol_a


@pytest.mark.parametrize("arch", BUILT)
def test_closed_form_matches_init_params(arch):
    """The closed form counts exactly the weights ``init_params`` allocates,
    in every (active_only, exclude_embed) accounting."""
    cfg = get_arch(arch)
    for a in (False, True):
        for e in (False, True):
            assert count_params(cfg, active_only=a, exclude_embed=e) == (
                model.count_params_analytic(cfg, active_only=a, exclude_embed=e)
            ), (a, e)


def _tokens(cfg, key, b, s):
    shape = (b, s, cfg.num_codebooks) if cfg.num_codebooks > 1 else (b, s)
    return jax.random.randint(key, shape, 0, cfg.vocab_size, dtype=jnp.int32)


@pytest.mark.parametrize("arch", BUILT)
def test_reduced_forward_and_train_step(arch):
    cfg = get_arch(arch).reduced()
    key = jax.random.PRNGKey(0)
    params, axes = model.init_params(cfg, key)
    # axes tree mirrors params tree
    assert jax.tree.structure(
        jax.tree.map(lambda _: 0, params)
    ) == jax.tree.structure(
        jax.tree.map(lambda _: 0, axes, is_leaf=lambda x: isinstance(x, tuple))
    )
    b, s = 2, 32
    toks = _tokens(cfg, key, b, s)
    logits, aux = model.forward(cfg, params, toks)
    want_shape = (
        (b, s, cfg.num_codebooks, cfg.vocab_size)
        if cfg.num_codebooks > 1
        else (b, s, cfg.vocab_size)
    )
    assert logits.shape == want_shape
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    batch = {"tokens": toks, "labels": toks}
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: model.loss_fn(cfg, p, batch), has_aux=True
    )(params)
    assert bool(jnp.isfinite(loss))
    gnorm = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    assert bool(jnp.isfinite(gnorm)) and float(gnorm) > 0


@pytest.mark.parametrize("arch", BUILT)
def test_reduced_decode_step(arch):
    cfg = get_arch(arch).reduced()
    key = jax.random.PRNGKey(1)
    params, _ = model.init_params(cfg, key)
    b = 2
    cache, caxes = model.init_cache(cfg, b, 16)
    tok = _tokens(cfg, key, b, 1)
    logits, cache2 = model.decode_step(cfg, params, cache, tok, jnp.int32(0))
    want = (b, cfg.num_codebooks, cfg.vocab_size) if cfg.num_codebooks > 1 else (b, cfg.vocab_size)
    assert logits.shape == want
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    # cache structure preserved
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)
    for a, bb in zip(jax.tree.leaves(cache), jax.tree.leaves(cache2)):
        assert a.shape == bb.shape and a.dtype == bb.dtype


def test_cell_matrix_counts():
    """36 runnable cells: 11 archs x 4 shapes - 8 long_500k skips."""
    cells = all_cells()
    assert len(cells) == 36
    skipped = [
        a for a in ARCH_IDS if not applicable(get_arch(a), SHAPES["long_500k"])
    ]
    assert len(skipped) == 8
    for a in ("jamba_v01_52b", "xlstm_1p3b", "mixtral_8x7b"):
        assert (a, "long_500k") in cells


def test_builds_covers_every_other_arch():
    """Only latent attention and leading dense layers fall outside the LM
    stack, and it refuses them, parameters and forward alike, rather than
    building a wrong model."""
    assert set(ARCH_IDS) - set(BUILT) == {"deepseek_v3"}
    cfg = get_arch("deepseek_v3").reduced()
    with pytest.raises(NotImplementedError, match="latent attention"):
        model.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="latent attention"):
        model.forward(cfg, {}, _tokens(cfg, jax.random.PRNGKey(1), 1, 4))


def test_mixtral_window_bounds_cache():
    cfg = get_arch("mixtral_8x7b")
    assert model.cache_len_for(cfg, 524288) == 4096
    cfg_full = get_arch("yi_6b")
    assert model.cache_len_for(cfg_full, 32768) == 32768
