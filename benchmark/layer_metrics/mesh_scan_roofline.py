"""`scan_roofline` under the mesh cell's name: Q6 and Q1's unavoidable
bytes at the chips' HBM peak (`peaks.json` times `device.count`) over
the devices' average busy time inside those statements' client spans.
`scan_roofline.read` computes exactly this for any number of chips, and
its `workloads` list is not a `model_config` PR's to extend: this file
calls it and counts no byte of its own. The next `benchmark` PR puts the
cell on `scan_roofline`'s list and deletes this alias (PERF.md, Open
questions)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_layer_metrics_scan_roofline",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "scan_roofline.py"))
scan_roofline = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(scan_roofline)


def read(run):
    return scan_roofline.read(run)
