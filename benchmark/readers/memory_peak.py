"""Peak bytes in use on the fullest chip since the process started
(``memory_stats()["peak_bytes_in_use"]``) over the chip's published memory."""


def read(run, params, env):
    if env["peaks"] is None:
        return None
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in env["devices"]),
               default=0)
    return 100.0 * peak / env["peaks"]["hbm_bytes"] if peak else None
