"""Share of the window the driver spent on host work around the device:
``driver.pad_pack``, ``driver.device_put`` and ``driver.decode`` span
seconds over the window's seconds, in percent."""

SPANS = ("driver.pad_pack", "driver.device_put", "driver.decode")


def read(run):
    seen = [run.spans[s] for s in SPANS if s in run.spans]
    if not seen:
        return None
    return 100.0 * sum(seconds for _, seconds in seen) / run.seconds
