"""Device milliseconds of a query that no stage of the fused pipeline
names: instructions without a scope in their op_name (copies,
broadcasts, collectives on a mesh), programs outside the catalogue
(`jit_tidb_mask_copy`), operations outside every program's run, and
operations the catalogue's entries disagree on. From the trace's
operations, each joined to the stage its program's catalogue
(`tidb_tpu_kernel_stage_ops`) gives its instruction; the six `stage_*`
metrics sum to the device's busy time a statement. See
`kernel_stages.py`."""
import kernel_stages


def read(run):
    return kernel_stages.ms_per_query(run, "none")
