"""Config registry + analytic parameter-count sanity."""
import pytest

from repro.configs import (ARCH_REGISTRY, ASSIGNED_ARCHS, SHAPES, get_config,
                           reduced, shape_applicable)

EXPECTED = {
    # arch -> (published total params, tolerance fraction)
    "internlm2-1.8b": (1.89e9, 0.25),
    "granite-8b": (8.1e9, 0.25),
    "nemotron-4-340b": (340e9, 0.20),
    "gemma3-12b": (12e9, 0.35),
    "xlstm-125m": (125e6, 0.6),
    "internvl2-2b": (1.9e9, 0.3),        # LM backbone only (ViT is a stub)
    "llama4-maverick-400b-a17b": (400e9, 0.25),
    "llama4-scout-17b-a16e": (109e9, 0.30),
    "jamba-v0.1-52b": (52e9, 0.30),
    "jamba2-3b": (3.03e9, 0.02),         # 3,029,337,472 weights held
}


def test_registry_has_all_assigned():
    assert len(ASSIGNED_ARCHS) == 11
    for a in ASSIGNED_ARCHS:
        assert get_config(a).name == a


def test_unknown_arch_raises():
    with pytest.raises(KeyError):
        get_config("nonexistent-model")


@pytest.mark.parametrize("arch,expected", sorted(EXPECTED.items()))
def test_param_counts_match_published(arch, expected):
    target, tol = expected
    n = get_config(arch).param_count()
    assert abs(n - target) / target < tol, \
        f"{arch}: analytic {n:.3g} vs published {target:.3g}"


def test_moe_active_params():
    mav = get_config("llama4-maverick-400b-a17b")
    assert mav.active_param_count() < 0.1 * mav.param_count()
    scout = get_config("llama4-scout-17b-a16e")
    assert scout.active_param_count() < 0.35 * scout.param_count()


def test_shape_grid():
    assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                           "long_500k"}
    # long_500k only for sub-quadratic archs
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        ok, why = shape_applicable(cfg, SHAPES["long_500k"])
        assert ok == cfg.subquadratic, (arch, why)
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert shape_applicable(cfg, SHAPES[s])[0]
    live = sum(1 for a in ASSIGNED_ARCHS for s in SHAPES.values()
               if shape_applicable(get_config(a), s)[0])
    assert live == 37   # 33 universal + 4 subquadratic long_500k


@pytest.mark.parametrize("arch", sorted(ASSIGNED_ARCHS))
def test_reduced_configs_are_small(arch):
    r = reduced(get_config(arch))
    assert r.d_model <= 128 and r.vocab_size <= 512
    assert r.param_count() < 5e6
    # family-defining structure is preserved
    full = get_config(arch)
    assert r.family == full.family
    assert (r.moe is None) == (full.moe is None)
    assert (r.ssm is None) == (full.ssm is None)
    assert r.attn_every == full.attn_every
