"""``peak_bytes_in_use`` of the fullest chip after the window, in GB --
where the program's steps set it.  The counter is the process's
lifetime peak; where it stood as high before the first step (set-up
built the model there) it says nothing of the step, and the reader
returns nothing."""


def read(env):
    peak = env.res.get("memory_peak_bytes")
    built = env.res.get("memory_peak_built_bytes", 0)
    return peak / 1e9 if peak and peak > built else None
