"""Host time of ``RequestStreamDriver.route_batch`` per batch: the time
spanned by the program's ``serve.route_batch`` spans less the device's busy
time inside them, over the window's batches.  The caller's copy back of the
routes lies outside the span."""


def read(view):
    span_s, busy_s = view["trace"].busy_in("serve.route_batch")
    batches = view["facts"].get("batches", 0)
    if span_s <= 0 or batches == 0:
        return None
    return 1e3 * (span_s - busy_s) / batches
