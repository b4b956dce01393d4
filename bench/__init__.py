"""The on-chip benchmark: one cell per run, driven by `bench/run.py`.

Everything that defines a measurement lives here so that the program under
test can change without moving the yardstick: traffic generators
(`traffic/`), plain references (`reference/`), the reduction from profiler
traces to metrics (`trace_reduce`), the table of device peaks (`peaks`) and
the least bytes each unit of work needs (`bytes`).  Cells, configurations,
drivers and per-layer metric readers are files found by name.
"""
