"""Mean ms from a request's admission to its first generated token, over
the first tokens the window committed (``ServingEngine.metrics``
``admit_to_first_s`` over ``first_tokens``, the window's deltas): the
chunk windows a prompt rides after its admission."""


def read(run):
    r = run.record
    if r["kind"] != "lm" or not r["engine"].get("first_tokens"):
        return None
    return 1e3 * r["engine"]["admit_to_first_s"] / r["engine"]["first_tokens"]
