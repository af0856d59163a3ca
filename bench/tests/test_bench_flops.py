"""The FLOP count behind ``mfu``, pinned to the hand count of the paper's
two networks."""
import pytest

from benchlib import cells, flops


@pytest.mark.parametrize("config, per_frame", [
    # conv 32x8x8 s4, 64x4x4 s2, 64x3x3 s1, dense 3136->512, heads 512->3+1
    ("paac_nature", 18_690_048),
    # conv 16x8x8 s4, 32x4x4 s2, dense 2592->256, heads 256->3+1
    ("paac_nips", 5_933_056),
])
def test_forward_flops_per_frame(config, per_frame):
    cfg = cells.load_json(cells.BENCH / "configs" / f"{config}.json")
    assert flops.forward_flops_per_frame(cfg) == per_frame


@pytest.mark.parametrize("t_max", [1, 5, 20])
def test_flops_per_timestep_counts_three_passes_and_the_bootstrap(t_max):
    cfg = cells.load_json(cells.BENCH / "configs" / "paac_nature.json")
    f = flops.forward_flops_per_frame(cfg)
    assert flops.flops_per_timestep(cfg, t_max) == pytest.approx(
        f * (3 + 1 / t_max), rel=1e-12)


def test_nature_and_nips_per_timestep_at_the_papers_t_max():
    nature = cells.load_json(cells.BENCH / "configs" / "paac_nature.json")
    nips = cells.load_json(cells.BENCH / "configs" / "paac_nips.json")
    assert flops.flops_per_timestep(nature, 5) == pytest.approx(59.808e6, rel=1e-4)
    assert flops.flops_per_timestep(nips, 5) == pytest.approx(18.986e6, rel=1e-4)
