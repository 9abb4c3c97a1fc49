"""Share of the cell's HBM that resident columns hold when the window
closes: the store's gauge `tidb_tpu_device_resident_bytes`, summed over
its placement specs, over `hbm_bytes` of peaks.json times the chips. A
program without the gauge reports nothing, and so does a device that
peaks.json does not know (the CPU rehearsal): a share of no stated HBM
is no number."""

GAUGE = "tidb_tpu_device_resident_bytes"


def read(run):
    after = run["growth"].after["metrics"]
    held = [v for (name, _), v in after.items() if name == GAUGE]
    dev = run["device"]
    if not held or dev["kind"] not in run["peaks"]:
        return None
    hbm = run["peaks"][dev["kind"]]["hbm_bytes"] * dev["count"]
    return 100.0 * sum(held) / hbm
