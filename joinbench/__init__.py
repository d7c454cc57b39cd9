"""joinbench: the benchmark of the radix join, driven by data.

One run of one cell::

    python3 -m joinbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, metrics and bounds are in ``BENCHMARK.json`` at the root of the
checkout.  Everything that belongs to one configuration, traffic mix or
metric sits in a file of its own under this directory, found by its name:

* ``configs/<config>.json``  -- a deployment: sizes, guarantees, source;
* ``traffic/<mix>.json``     -- the parameters of one traffic mix; its
  ``loop`` key names the generator in ``loops/<loop>.py`` that reads it;
* ``metrics/<metric>.py``    -- one reader per metric, ``read(run)``.

The yardstick lives here too: the data generator and its NumPy twin
(``datagen``), the plain reference (``reference``), the trace reduction
(``trace``), the peaks table (``peaks``) and the bytes a join must move
(``work``).  From the program the benchmark takes only the engine under
test (``HashJoin``) and its timers and counters.
"""
