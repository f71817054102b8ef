"""Reader of `device_us_per_instr`: device busy time of the window, as the
traced slices sample it, over the guest instructions of the window."""


def device_us_per_instr(traced: dict):
    """`traced` is the harness's reduction of the run's device traces:
    busy_s and window_s summed over the slices, span_s and instructions of
    the measured window.  Nothing to read where the trace or the count is
    missing."""
    if not traced or not traced.get("instructions") \
            or not traced.get("window_s"):
        return None
    busy_share = traced["busy_s"] / traced["window_s"]
    return busy_share * traced["span_s"] * 1e6 / traced["instructions"]
