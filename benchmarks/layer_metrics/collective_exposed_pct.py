"""Share of the traced window in which a device ran a collective and no computation, averaged over the devices."""

def read(records):
    trace = records["trace"]
    if trace is None:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"], "%"
