"""Metric readers: one module per metric, ``UNIT`` and ``read(cell)``.

``read`` returns the number, or None when the run holds nothing to read it
from (the harness then leaves the metric out of the line; it never stands
in a 0). ``cell`` is ``run_cell.Cell``: the window's steps with their walls
on the host clock, ``window_s``, ``setup_s``, ``sky_s_per_step``, and in a
traced run ``trace_summary`` (``trace_reduce.reduce_trace``) and
``telemetry`` (``trace_reduce.read_telemetry``).
"""
