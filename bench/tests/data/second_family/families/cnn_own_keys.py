"""Fixture family ``cnn_own_keys``: the paper's CNN with its widths under
keys of its own (``frame_shape``, ``conv_layers``, ``hidden``), translated
to and from the ``paac_cnn`` family's (``obs_shape``, ``convs``,
``dense``). ``test_bench_families.py`` adds it to a copy of ``bench/``,
with a configuration and a workload, to show that a family joins the
benchmark by new files alone."""
from benchlib.flops import flops_per_timestep as _cnn_flops
from benchlib.paper_job import paper_job
from reference import paac

FAULTS = paac.FAULTS
OWN_KEYS = {"frame_shape": "obs_shape", "conv_layers": "convs",
            "hidden": "dense"}


def _as_cnn(config: dict) -> dict:
    cnn = {k: v for k, v in config.items() if k not in OWN_KEYS}
    cnn.update({theirs: config[ours] for ours, theirs in OWN_KEYS.items()})
    return cnn


def job(config: dict, n_envs: int, t_max: int):
    env, agent, settings = paper_job(_as_cnn(config), n_envs, t_max)
    for ours, theirs in OWN_KEYS.items():
        settings[ours] = settings.pop(theirs)
    return env, agent, settings


def flops_per_timestep(config: dict, t_max: int) -> float:
    return _cnn_flops(_as_cnn(config), t_max)


def train(config: dict, seed: int, **layout):
    return paac.train(_as_cnn(config), seed, **layout)
