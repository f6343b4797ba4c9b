"""VMM data plane (core/vmm.py, core/scheduler.py): host microseconds
per mediated call spent outside the compiled callable, that is the
benchmark's timer around ``tenant.device.run`` less its timer around the
callable inside. Calls in the untraced part of the window."""


def read(run):
    lo, hi = run.win.preroll, run.win.host_end
    t0 = run.served.get("t0", 0.0)
    calls = [(o - i) for t, o, i in run.vmm_calls if lo <= t - t0 < hi]
    if not calls:
        return None
    return 1e6 * sum(calls) / len(calls)
