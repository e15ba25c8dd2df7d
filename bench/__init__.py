"""On-chip benchmark of the MoE serving path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the chip it is
started on and prints one JSON line.  Everything a cell needs is found
by name: ``configs/<config>.json`` (model, cut, deployment, engine
settings), ``arch/<model_type>.py`` (the architecture its
configuration's ``model_type`` names), ``traffic/<mix>.json``
(generator parameters) and ``metrics/<metric>.py`` (one reader per
per-layer metric).

The modules here are the yardstick: traffic generation (``traffic``),
the seeded weights' key scheme (``weights``), each family's sizes,
weights, float32 reference that decides ``correct`` and work counts
(``arch/``; the Qwen family's in ``sizes``, ``weights`` and
``reference``), the shared work counts and peaks (``work``,
``peaks.json``) and the trace reduction (``trace``).  ``serve`` (the
engine), ``batch`` (static batches through the launch steps) and the
architecture modules import the program under test; ``witness`` and the
tests reach into it.
"""
