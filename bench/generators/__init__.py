"""Traffic generator plug-ins: one file per generator, chosen by a traffic
mix's ``generator`` (``plugins.generator``). A new mix that an existing
generator can make is a data file under ``traffic/`` alone; a new kind of
traffic is a file here.

A plug-in is a module with two functions:

* ``make(config, traffic, seed)``: the fleet's data from the seed, a dict
  {"clients": [{"x", "y"}], "server": {"x", "y"}, "test": {"x", "y"},
  "counts"} as ``repro.core.FedS3ATrainer`` takes it. The same seed gives
  the same data.
* ``small(config, traffic)``: the (config, traffic) pair cut to a size a
  CPU test holds, as new dicts; the cell's limits stay as they are.
"""
