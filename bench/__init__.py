"""Chip benchmark of the MCMC inference path: back-to-back ``MCMC.run`` fits.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once.  Everything that belongs to one
configuration, traffic mix, cell or per-layer metric lives in a file of its
own, found by the name ``BENCHMARK.json`` gives it (see ``spec.py``).
"""
