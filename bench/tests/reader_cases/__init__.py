"""Case tables of the metric readers in ``bench/metrics/``, one module
each, found by ``test_bench_metrics.py`` without a list to edit. A
module holds:

- ``CASES``: reader name -> the value worked out by hand;
- ``context(traced)``: a context manager that sets up what its readers
  read and yields the ``Context`` of its cases, with their trace or,
  where ``traced`` is false, with none; it undoes its set-up on exit.

A reader joins with its case as two new files, the reader and a case
table, and no existing file edited. No name has cases in two
tables."""
