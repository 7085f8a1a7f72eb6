"""replica.host_ms_per_window: host time inside ``Replica.step`` that is not
spent waiting on the device (``jax.device_get``, timed by the benchmark),
per retired decode window, in ms, over the whole run."""


def read(art):
    run = art["run"]
    if not run.windows_total:
        return None
    return (run.step_s - art["host_wait_s"]) / run.windows_total * 1e3
