"""Device time of HLO ``sort`` ops (the sessionizer's and the dedup's
``lexsort_perm`` passes and the tick's row partitions), summed over the
devices, per event of the traced window (device trace)."""


def read(ctx):
    t = ctx["trace"]
    secs = t and t["op_kind_s"].get("sort")
    if not secs:
        return None
    return secs * 1e9 / ctx["window"]["events"]
