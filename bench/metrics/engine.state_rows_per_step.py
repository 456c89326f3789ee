"""Rows whose recurrent state a step program advanced, per step, decode
and chunk windows together (``ServingEngine.metrics``
``state_rows_stepped`` over ``decode_steps``, the window's deltas). No
reading where the program counts no recurrent state rows."""


def read(run):
    r = run.record
    if r["kind"] != "lm" or "state_rows_stepped" not in r["engine"] \
            or not r["engine"]["decode_steps"]:
        return None
    return r["engine"]["state_rows_stepped"] / r["engine"]["decode_steps"]
