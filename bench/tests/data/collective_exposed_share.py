"""Share of the traced window in which a collective op (the keyed
``all_to_all`` repartition, the rollups' ``psum``) ran on a device with
no other op beside it, averaged over the devices (device trace)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not t["collective_s"]:
        return None
    return t["collective_exposed_s"] / t["window_s"]
