"""The decode program's share of its memory roofline in a hybrid cell:
the bytes its calls must move (``bench/work_hybrid.py``: weights per
call, the state of each stepped row read and written, the attention
layers' held K/V and q/out) at the chip's HBM peak, over the decode
program's device time in the trace, in %. No reading where the program
counts no recurrent state rows."""
from bench.work_hybrid import Hybrid


def read(run):
    r = run.record
    p = (run.trace or {}).get("programs", {}).get("decode")
    w = r.get("work")
    if r["kind"] != "lm" or not p or not p["device_s"] or w is None \
            or not w["decode"].calls or "state_rows_stepped" not in r["engine"]:
        return None
    nbytes = Hybrid.from_config(run.config).decode_bytes(w["decode"])
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / p["device_s"]
