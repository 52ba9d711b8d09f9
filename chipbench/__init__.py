"""On-chip benchmark of the distributed FFT: ``run.py``, ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or metric is
a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``   -- the deployment: grid, kinds or topology,
  dtype, mesh, which entry builds it and which reference checks it;
* ``traffic/<mix>.json``      -- what one step is and how inputs are made;
* ``limits/<cell>.json``      -- the limit of each number ``correct`` compares;
* ``metrics/<metric>.py``     -- ``read(run)``: one metric from a finished run;
* ``entries/<entry>.py``      -- ``build(cfg, devices)``: the program's public
  entry for a configuration;
* ``references/<ref>.py``     -- ``error(op, inp, out, cfg, rng)``: the float64
  reference of an entry's operations.
"""
