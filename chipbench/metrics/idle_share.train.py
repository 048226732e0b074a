"""Share of the traced window in which no operation ran on the device,
averaged over the cell's devices (training cells). Percent."""


def read(red, rec, peaks):
    if rec.get("kind") != "train" or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
