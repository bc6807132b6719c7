"""Table III's clients at a scale (``cicids.make_dataset``): one client per
row of the configuration's scenario."""
from __future__ import annotations

import copy

import cicids


def make(config, traffic, seed):
    fleet = config["fleet"]
    return cicids.make_dataset(
        fleet["scenario"], scale=traffic["scale"],
        server_frac=fleet["server_frac"], test_frac=traffic["test_frac"],
        seed=seed, separation=traffic["separation"])


def small(config, traffic):
    """A hundredth of the counts."""
    return copy.deepcopy(config), dict(traffic, scale=0.01)
