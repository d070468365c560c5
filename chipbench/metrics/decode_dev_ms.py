"""Device time per run of the engine's decode program (``jit_decode_all``)
in the traced window (ms)."""


def read(obs):
    red = obs.get("trace")
    rec = red and red["modules"].get("jit_decode_all")
    if not rec or not rec[1]:
        return None
    return 1e3 * rec[0] / rec[1]
