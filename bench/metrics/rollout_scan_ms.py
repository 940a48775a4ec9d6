"""Host time of one fused device scan, from launch until its trajectory is
on the host: the rollout workers' ``scan_time_s`` over their scans."""

def read(w):
    scans = w.d("rollout_scans")
    seconds = w.d("rollout_scan_s")
    if not scans or seconds is None:
        return None
    return 1e3 * seconds / scans
