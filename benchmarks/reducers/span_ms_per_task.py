"""Seconds of one span inside the window per steady task, in ms."""


def reduce(record, name: str):
    tasks = record.client.get("steady_tasks")
    if not record.spans or not tasks:
        return None
    return 1000.0 * sum(record.spans_in_window(name)) / tasks
