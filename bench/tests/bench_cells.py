"""Drive a whole benchmark run on the CPU at a small size, past the look
for a chip: the loops, the references and the checks that decide
``correct``."""

import copy
import types

SEED = 2**31 + 11


QUERY = ("graph500-s16", "query_closed")


def load(config: str, traffic: str):
    from bench import run as R

    return (R.load_json(f"{R.HERE}/configs/{config}.json"),
            R.load_json(f"{R.HERE}/traffic/{traffic}.json"))


def run_cell(deployment, seconds: float = 1.5, scale: int = 8,
             trace: int = 0, cfg_over=None, **mix_over):
    """Run ``deployment`` (configuration, traffic) as a one-chip cell."""
    import jax

    from bench import run as R

    bench = R.load_json(f"{R.ROOT}/BENCHMARK.json")
    cfg, mix = load(*deployment)
    cells = [w for w in bench["workloads"]
             if (w["config"], w["traffic"]) == deployment]
    cell = cells[0] if cells else {"name": "-".join(deployment), "chips": 1}
    cfg = copy.deepcopy(cfg)
    cfg["generator"]["scale"] = scale
    cfg.update(cfg_over or {})
    mix.update(mix_over)
    args = types.SimpleNamespace(seed=SEED, seconds=seconds, trace=trace)
    return R.execute(jax, bench, cell, cfg, mix, args)
