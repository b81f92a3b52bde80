"""Architecture configs: counts, registry, applicability matrix."""

import pytest

from repro.configs import (SHAPES, all_archs, all_cells, applicable,
                           get_arch, get_shape)

# labelled sizes from the assignment (total params, billions)
LABELED = {
    "hubert-xlarge": (0.9, 1.1),
    "qwen2-vl-72b": (70, 75),
    "mamba2-2.7b": (2.6, 2.8),
    "granite-moe-1b-a400m": (1.2, 1.5),
    "llama4-maverick-400b-a17b": (380, 420),
    "qwen3-8b": (7.5, 8.5),
    "deepseek-7b": (6.5, 7.2),
    "deepseek-coder-33b": (32, 34.5),
    "minitron-8b": (7.3, 8.6),
    "hymba-1.5b": (1.4, 1.8),
    "deepseek-v2-lite": (15.5, 16.0),
}

ACTIVE = {
    "granite-moe-1b-a400m": (0.35, 0.5),
    "llama4-maverick-400b-a17b": (16, 18.5),
}


@pytest.mark.parametrize("name", all_archs())
def test_param_count_matches_label(name):
    lo, hi = LABELED[name]
    n = get_arch(name).param_count() / 1e9
    assert lo <= n <= hi, f"{name}: {n:.2f}B outside [{lo},{hi}]"


@pytest.mark.parametrize("name", sorted(ACTIVE))
def test_active_params(name):
    lo, hi = ACTIVE[name]
    n = get_arch(name).active_param_count() / 1e9
    assert lo <= n <= hi, f"{name}: active {n:.2f}B outside [{lo},{hi}]"


def test_cells_total_40():
    cells = all_cells()
    assert len(cells) == 44
    runnable = [c for c in cells if c[2]]
    skipped = [c for c in cells if not c[2]]
    assert len(skipped) == 10         # DESIGN.md §5, + deepseek-v2-lite
    assert len(runnable) == 34
    for _, _, ok, why in skipped:
        assert why


def test_encoder_skips_decode():
    a = get_arch("hubert-xlarge")
    assert not applicable(a, SHAPES["decode_32k"])[0]
    assert not applicable(a, SHAPES["long_500k"])[0]
    assert applicable(a, SHAPES["prefill_32k"])[0]


def test_long_context_only_subquadratic():
    for name in all_archs():
        a = get_arch(name)
        ok, _ = applicable(a, SHAPES["long_500k"])
        assert ok == a.sub_quadratic or a.is_encoder and not ok


@pytest.mark.parametrize("name", all_archs())
def test_reduced_configs_are_small(name):
    r = get_arch(name).reduced()
    assert r.d_model <= 128 and r.n_layers <= 4
    assert r.param_count() < 5e7
    assert r.family == get_arch(name).family


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        get_arch("nope")
    with pytest.raises(KeyError):
        get_shape("nope")
