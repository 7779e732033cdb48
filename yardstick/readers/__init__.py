"""Readers of per-layer metrics. A reader module has
``read(facts, args) -> number or None``; None leaves the metric out of the
line. ``facts`` is what one traced run observed:

``kind``, ``seconds``, ``attempted``, ``bound``: the cell's traffic kind,
its ``--seconds``, pods created for the window and pods bound in it;
``groups``: [[due_s, n_pods, sent_s, returned_s, error], ...] from the
sender's clock, seconds after the window's origin;
``spans``: {span name: {"ms": total, "n": count}} of the program's spans
inside the window;
``counters``: {series: increase over the window} — every series of the
program's Prometheus exposition by its exposed name, ``ctx.<key>`` of the
resident context and ``compile.real`` / ``compile.backend`` /
``compile.hits`` of the compile meter (program.counters);
``trace``: what xplane.reduce made of the profiler trace, or None.
"""

DRAINS = "scheduler_pipeline_depth_count"  # one observation a dispatch


def drains(facts) -> float:
    return facts["counters"].get(DRAINS, 0.0)
