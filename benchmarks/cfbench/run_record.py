"""What a driver hands to the reducers: one record of one run."""
import dataclasses


@dataclasses.dataclass
class RunRecord:
    """Everything a metric may be reduced from.

    ``window`` is (start, end) on ``time.time()``: the measured window.
    ``client`` holds what the benchmark saw from outside (commit times,
    request latencies, set-up seconds): the only source of end-to-end
    metrics. ``spans``, ``counters`` and ``programs`` are the program's
    own records and exist only in a traced run, as does ``trace`` (the
    device trace as :mod:`cfbench.trace` tables).
    """
    cell: dict
    config: dict
    traffic: dict
    device: dict
    window: tuple = (0.0, 0.0)
    client: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    programs: list = dataclasses.field(default_factory=list)
    trace: dict = None
    attempted: int = 0
    failed: int = 0
    correct: bool = False
    notes: list = dataclasses.field(default_factory=list)
    # what ``correct`` compared: name -> {"value", "limit"}
    checks: dict = dataclasses.field(default_factory=dict)

    def spans_in_window(self, name: str) -> list:
        """Spans of ``name`` that ended inside the measured window, each
        clipped to the part of it that lies inside."""
        start, end = self.window
        out = []
        for span in self.spans:
            if span.get("name") != name or not start <= span["t"] <= end:
                continue
            out.append(min(span["dur_s"], span["t"] - start))
        return out
