"""Process liveness, for the sweepers of dead runs' leftovers."""

import os


def pid_alive(pid: int) -> bool:
    """Portable liveness probe — /proc is Linux-only, and the shm bus
    deliberately runs on macOS x86-64 too (its tempdir fallback):
    a /proc check there reads EVERY run as dead and the sweeper would
    unlink a live job's rings out from under it. Signal 0 probes
    without sending; EPERM means alive-but-not-ours."""
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except OSError:
        return True
    return True
