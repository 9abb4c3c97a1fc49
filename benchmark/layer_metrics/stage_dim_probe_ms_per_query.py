"""Device milliseconds of a query in the `dim_probe` stage of the fused
pipeline: probing the dimensions: the gathers of a join's build-side
positions and payload at fact width. From the trace's operations, each
joined to the stage its program's catalogue
(`tidb_tpu_kernel_stage_ops`) gives its instruction; the six `stage_*`
metrics sum to the device's busy time a statement. Also logs the tables
`PERF.md` section 5 is written from. See `kernel_stages.py`."""
import kernel_stages


def read(run):
    kernel_stages.log_tables(run)
    return kernel_stages.ms_per_query(run, "dim_probe")
