"""device_idle.train: the devices' idle share over the traced training
window, 1 − busy/window, in percent."""


def read(tr, record, cell, device):
    if not device["window_s"]:
        return None
    return (1.0 - device["busy_s"] / device["window_s"]) * 100.0
