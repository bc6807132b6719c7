"""Table III's rows tiled over a fleet of ``fleet.clients`` at a scale, with
size jitter drawn once from ``size_seed`` (``cicids.make_fleet_dataset``)."""
from __future__ import annotations

import copy

import cicids


def make(config, traffic, seed):
    fleet = config["fleet"]
    return cicids.make_fleet_dataset(
        fleet["clients"], scenario=fleet["scenario"], scale=traffic["scale"],
        jitter=traffic["jitter"], size_seed=traffic["size_seed"],
        server_frac=fleet["server_frac"], test_frac=traffic["test_frac"],
        seed=seed, separation=traffic["separation"])


def small(config, traffic):
    """20 clients at 3% of a row each."""
    config = copy.deepcopy(config)
    config["fleet"]["clients"] = 20
    return config, dict(traffic, scale=0.03)
