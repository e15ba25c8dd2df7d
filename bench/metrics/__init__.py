"""One reader per metric: ``<metric>.py`` defines ``read(run)``, which
takes the run's record (``bench.run.Run``) to the metric's value, or to
None where the run holds nothing to read (the metric is then left out
of the result line)."""
