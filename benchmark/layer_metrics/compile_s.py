"""``compile_s``: host clock around ``.compile()`` in set-up — XLA's
compile, or the load from the persistent cache (an earlier line of the
run says which)."""


def read(run):
    return run["spans"].get("compile_s")
