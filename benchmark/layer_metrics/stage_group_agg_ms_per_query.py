"""Device milliseconds of a query in the `group_agg` stage of the fused
pipeline: grouping and aggregating: run boundaries, segment sums, the
dense slots. From the trace's operations, each joined to the stage its
program's catalogue (`tidb_tpu_kernel_stage_ops`) gives its instruction;
the six `stage_*` metrics sum to the device's busy time a statement. See
`kernel_stages.py`."""
import kernel_stages


def read(run):
    return kernel_stages.ms_per_query(run, "group_agg")
