"""Device layer (XLA on the TPU): share of the traced window in which no
operation ran on a chip, from the union of the trace's op intervals; the
worst of the cell's chips."""


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    devs = list(run.trace["devices"].values())[:run.n_devices]
    w = run.trace["window_s"]
    return max(100.0 * (1.0 - d["busy_s"] / w) for d in devs)
