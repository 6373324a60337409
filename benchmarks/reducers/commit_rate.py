"""Work committed per second between the first and the last steady
commit: ``n * unit / (t_n - t_0)`` with ``t_0`` the commit of the last
warm-up task, so a task cut off by the window's end costs nothing."""


def reduce(record, unit_key: str = "task_voxels", scale: float = 1.0):
    times = record.client.get("steady_commit_times") or []
    if not times or times[-1] <= record.client["window_start"]:
        return None
    span = times[-1] - record.client["window_start"]
    return len(times) * record.client[unit_key] / span * scale
