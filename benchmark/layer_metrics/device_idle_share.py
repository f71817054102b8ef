"""Reader of `device_idle_share`: 1 - busy / traced interval."""


def device_idle_share(traced: dict):
    if not traced or not traced.get("window_s"):
        return None
    return 100.0 * (1.0 - traced["busy_s"] / traced["window_s"])
