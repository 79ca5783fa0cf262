"""Reader kinds: each module has `read(ctx, spec)`, which returns the
metric's value or None where there is nothing to read.  `spec` is the
metric's file under `layer_metrics/`; `ctx` is `run.Context`."""
