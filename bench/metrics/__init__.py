"""One reader per per-layer metric, named as the metric.  Each has
``read(ctx) -> float | None``; ``ctx`` holds the trace summary
(``trace``), the configuration (``cfg``), its model module (``model``),
the chip's peaks (``peaks``) and the traffic driver's counts.  A reader that
finds nothing to read returns None and the metric is left out."""
