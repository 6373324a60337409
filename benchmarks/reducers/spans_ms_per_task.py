"""Seconds of several spans inside the window per steady task, in ms: one
quantity that the program times in parts. None where the run has no span
of any of them (a program from before they were added)."""


def reduce(record, names: list):
    tasks = record.client.get("steady_tasks")
    if not record.spans or not tasks:
        return None
    seconds = [record.spans_in_window(name) for name in names]
    if not any(seconds):
        return None
    return 1000.0 * sum(sum(part) for part in seconds) / tasks
