"""1 - the union of device-operation intervals over the traced window,
in percent, on the chip that holds the leader (the only chip where all
replicas share one)."""

from perfbench.readers._trace import devices


def read(spec, view):
    devs = devices(view)
    if not devs:
        return None
    want = view["deployment"].leader_device_id()
    name = next((k for k in devs if want is not None
                 and k.endswith(f":{want}")), sorted(devs)[0])
    return 100.0 * (1.0 - devs[name]["busy_s"] / view["trace"]["window_s"])
