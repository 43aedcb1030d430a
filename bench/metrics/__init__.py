"""One reader per per-layer metric, found by the metric's name. Each
``read(ctx)`` takes the run's records (``ctx["records"]``), the reduced
trace (``ctx["trace"]``) and the chip's peaks (``ctx["peaks"]``), and
returns the number, or None where it finds nothing to read."""
