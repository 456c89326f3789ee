"""Query positions the engine fed through its step programs per step,
decode and chunk windows together (``ServingEngine.metrics``
``kernel_positions`` over ``decode_steps``, the window's deltas)."""


def read(run):
    r = run.record
    if r["kind"] != "lm" or not r["engine"]["decode_steps"]:
        return None
    return r["engine"]["kernel_positions"] / r["engine"]["decode_steps"]
