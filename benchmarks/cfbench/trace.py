"""From a profiler trace to tables, and from tables to numbers.

``load_run_trace`` reads the ``.xplane.pb`` a ``jax.profiler`` session
leaves (``jax.profiler.ProfileData``, nothing but JAX) into plain tables:

    {"window_s": seconds from the first to the last event of the trace,
     "devices": [{"name": plane name,
                  "ops": [[name, category, start_ns, dur_ns], ...]}],
     "host": [[name, start_ns, dur_ns], ...]}      # host events >= 1 ms

``load`` reads such tables from gzipped JSON, which is how the recorded
v5e trace under ``tests/data`` is kept (``tests/record_trace.py`` wrote
it). Every reduction below works on the tables, so the test on the
recorded trace pins the same code the chip runs use.

An op's category is first read from the event's text (``parse_op``).
Where the run's programs say what is inside their ops (``programs.json``
``op_convolutions``), ``file_by_contents`` then decides once, for every
reducer, which ops are convolutions: by what a program lists under the
op's name, not by how XLA happened to name or wrap it.

Which planes are devices, and how ops are named on a v5e, is written up
in PERF.md ("Reading a v5e trace").
"""
import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_MIN_NS = 1_000_000

# On a v5e with jax 0.9.0 an op event's name is the whole HLO instruction,
# "%fusion.960 = bf16[20,256,32,34,3]{...} fusion(...), kind=kOutput,
# calls=...", and carries no category of its own. So the category is read
# from the text: the opcode, and for a fusion its kind. An output fusion
# (kOutput) is what the TPU compiler makes of a convolution or dot with its
# epilogue fused in, unless its name says it is rooted elsewhere
# (reduce-window: the max-pool). Where an event is a bare op name, the name
# rule of tools/analyze_trace.py applies. First match wins. This is the
# *text rule*: all there is for a run whose programs do not say what their
# ops hold, and the first guess that ``file_by_contents`` corrects.
CONVOLUTION = "convolution"
OUTPUT_FUSION = "output fusion"
_HLO = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<rest>.*)$", re.S)
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")
_SHAPE = re.compile(r"^\(?([a-z0-9]+\[[0-9,]*\])")
_NOT_CONV_ROOT = re.compile(r"reduce|scatter|dynamic-update-slice|sort",
                            re.I)
_FUSION_KINDS = {"kLoop": "loop fusion", "kInput": "input fusion",
                 "kCustom": "custom fusion"}
_NAME_RULES = (
    ("convolution", re.compile(r"conv", re.I)),
    ("collective", re.compile(
        r"all-reduce|all-gather|all-to-all|reduce-scatter|"
        r"collective-permute|psum|ppermute", re.I)),
    ("scatter", re.compile(r"scatter", re.I)),
    ("gather/slice", re.compile(r"gather|slice", re.I)),
    ("copy", re.compile(r"copy|transpose|reshape|bitcast", re.I)),
    ("custom-call", re.compile(r"custom-call|tpu_custom_call", re.I)),
    ("fusion", re.compile(r"fusion", re.I)),
)
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|reduce-scatter|collective|"
    r"permute|^send|^recv", re.I)


def parse_op(text: str):
    """(short name, category) of one op event."""
    match = _HLO.match(text)
    if not match:
        for category, rule in _NAME_RULES:
            if rule.search(text):
                return text, category
        return text, "other"
    name, rest = match.group("name"), match.group("rest")
    shape = _SHAPE.match(rest)
    short = f"{name} {shape.group(1)}" if shape else name
    opcode = _OPCODE.search(" " + rest)
    opcode = opcode.group(1) if opcode else "other"
    if opcode == "fusion":
        kind = _KIND.search(rest)
        kind = kind.group(1) if kind else ""
        if kind == "kOutput":
            if _NOT_CONV_ROOT.search(name):
                return short, OUTPUT_FUSION
            return short, CONVOLUTION
        return short, _FUSION_KINDS.get(kind, "fusion")
    return short, opcode


def is_collective(category: str) -> bool:
    return bool(_COLLECTIVE.search(category))


# ---------------------------------------------------------------------------
# what the programs say of their ops
# ---------------------------------------------------------------------------
def places_of_ops(programs: list):
    """``{op name: {(scope, part), ...}}`` over every program's
    ``op_parts``: one place where the programs agree. None if no program
    has the map."""
    names: dict = {}
    found = False
    for program in programs:
        for scope, by_part in (program.get("op_parts") or {}).items():
            found = True
            for part, ops in by_part.items():
                for op in ops:
                    names.setdefault(op, set()).add((scope, part))
    return names if found else None


def convolutions_of_ops(programs: list):
    """``{op name: [one list of [module path, window] a program that
    knows the op, empty where that program lists no convolution in it]}``
    over every program that carries ``op_convolutions``. A program knows
    the ops it places (``op_scopes``, ``op_parts``) and those it lists a
    convolution for. None if no program has the map (a program from
    before PR 40, a recorded trace without ``programs.json``)."""
    names: dict = {}
    found = False
    for program in programs:
        held = program.get("op_convolutions")
        if held is None:
            continue
        found = True
        known = set(held)
        for ops in (program.get("op_scopes") or {}).values():
            known.update(ops)
        for by_part in (program.get("op_parts") or {}).values():
            for ops in by_part.values():
                known.update(ops)
        for op in known:
            names.setdefault(op, []).append(held.get(op) or [])
    return names if found else None


def file_by_contents(tables: dict, programs: list) -> dict:
    """``tables`` with every op's category decided by what the run's
    programs say is inside it, for every reducer at once.

    Where some program carries ``op_convolutions``: an op is a
    ``convolution`` if and only if a program lists at least one
    convolution under its name, whatever its opcode (a fusion of any
    kind, a bare ``convolution``, a ``custom-call`` whose kernel the
    program names as one); a ``kind=kOutput`` fusion for which no program
    does (the pools' ``reduce_window`` on the folded array, PR 43) is an
    ``output fusion``. Every other op keeps the category of its text, a
    bare ``convolution`` instruction too: its text is the program's. A
    name that one program lists a convolution for and another knows
    without one keeps the text rule's answer and is counted.

    Where no program carries the map the tables come back as they are:
    the text rule stands and every reading is what it was.

    The result has one more key, ``filing``: ``{"by": "contents"`` or
    ``"text"``, ``"moved": {short name: [text's category, category]}``,
    ``"ambiguous": [op names]}``.
    """
    held = convolutions_of_ops(programs)
    if held is None:
        return {**tables, "filing": {"by": "text", "moved": {},
                                     "ambiguous": []}}
    # per op name: True where every program that knows it lists a
    # convolution, False where none does, None where they disagree
    lists = {name: (any(said) if any(said) == all(said) else None)
             for name, said in held.items()}
    moved, ambiguous, devices = {}, set(), []
    for device in tables["devices"]:
        ops = []
        for short, category, start, dur in device["ops"]:
            name = short.split(" ", 1)[0]
            listed = lists.get(name, False)
            filed = category
            if listed is None:
                ambiguous.add(name)
            elif listed:
                filed = CONVOLUTION
            elif category == CONVOLUTION and "fusion" in name:
                # the text rule's guess at a kOutput fusion; a bare
                # `convolution.3` is one by its opcode and stays
                filed = OUTPUT_FUSION
            if filed != category:
                moved[short] = [category, filed]
            ops.append([short, filed, start, dur])
        devices.append({**device, "ops": ops})
    return {**tables, "devices": devices,
            "filing": {"by": "contents", "moved": moved,
                       "ambiguous": sorted(ambiguous)}}


# ---------------------------------------------------------------------------
# xplane -> tables
# ---------------------------------------------------------------------------
def find_xplane(trace_dir: str):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def tables_from_xplane(path: str, chips: int) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    first, last = None, None

    def see(start, dur):
        nonlocal first, last
        first = start if first is None else min(first, start)
        last = start + dur if last is None else max(last, start + dur)

    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            if int(match.group(1)) >= chips:
                continue
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for event in line.events:
                    start, dur = int(event.start_ns), int(event.duration_ns)
                    ops.append([*parse_op(event.name), start, dur])
                    see(start, dur)
            devices.append({"name": plane.name, "ops": ops})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for event in line.events:
                    start, dur = int(event.start_ns), int(event.duration_ns)
                    see(start, dur)
                    if dur >= HOST_MIN_NS:
                        # "pjrt-tpu-tasks/316" -> "pjrt-tpu-tasks": the
                        # thread's number differs from run to run
                        thread = re.sub(r"/\d+$", "", line.name)
                        host.append([f"{thread}: {event.name}",
                                     start, dur])
    window_s = (last - first) / 1e9 if first is not None else 0.0
    devices.sort(key=lambda d: d["name"])
    return {"window_s": window_s, "t0_ns": first or 0, "t1_ns": last or 0,
            "devices": devices, "host": host}


def load_run_trace(trace_dir: str, chips: int) -> dict:
    path = find_xplane(trace_dir)
    if path is None:
        return {"window_s": 0.0, "t0_ns": 0, "t1_ns": 0, "devices": [],
                "host": []}
    return tables_from_xplane(path, chips)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# tables -> numbers
# ---------------------------------------------------------------------------
def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _busy(device: dict):
    return _union((op[2], op[2] + op[3]) for op in device["ops"])


def busy_seconds(tables: dict) -> list:
    """Per device, the seconds in which some op ran: the union of the op
    intervals, so nested or overlapping ops count once."""
    return [sum(e - s for s, e in _busy(d)) / 1e9
            for d in tables["devices"]] or [0.0]


def idle_shares(tables: dict) -> list:
    window = tables["window_s"]
    if not tables["devices"] or window <= 0:
        return []
    return [1.0 - busy / window for busy in busy_seconds(tables)]


def _leaf_seconds(device: dict) -> dict:
    """{(name, category): seconds} with every instant given to the
    innermost op running then, so a fusion's time is not counted again in
    the `while` or `call` that contains it."""
    ops = sorted(device["ops"], key=lambda op: (op[2], -op[3]))
    totals: dict = {}
    stack = []   # [name, category, end, cursor]

    def credit(entry, until):
        if until > entry[3]:
            key = (entry[0], entry[1])
            totals[key] = totals.get(key, 0) + (until - entry[3])
            entry[3] = until

    for name, category, start, dur in ops:
        end = start + dur
        while stack and stack[-1][2] <= start:
            done = stack.pop()
            credit(done, done[2])
            if stack:
                stack[-1][3] = max(stack[-1][3], done[2])
        if stack:
            credit(stack[-1], start)
        stack.append([name, category, end, start])
    while stack:
        done = stack.pop()
        credit(done, done[2])
        if stack:
            stack[-1][3] = max(stack[-1][3], done[2])
    return {key: ns / 1e9 for key, ns in totals.items()}


def category_seconds(tables: dict) -> list:
    """Per device, {category: seconds} of innermost-op time."""
    out = []
    for device in tables["devices"]:
        by_category: dict = {}
        for (_, category), seconds in _leaf_seconds(device).items():
            by_category[category] = by_category.get(category, 0.0) + seconds
        out.append(by_category)
    return out


def category_share(tables: dict, pattern: str):
    """Share of device busy time in categories matching ``pattern``
    (regex, case-insensitive), mean over devices."""
    rule = re.compile(pattern, re.I)
    shares = []
    for by_category, busy in zip(category_seconds(tables),
                                 busy_seconds(tables)):
        if busy > 0:
            hit = sum(s for c, s in by_category.items() if rule.search(c))
            shares.append(hit / busy)
    return sum(shares) / len(shares) if shares else None


def category_total_seconds(tables: dict, pattern: str) -> list:
    rule = re.compile(pattern, re.I)
    return [sum(s for c, s in by_category.items() if rule.search(c))
            for by_category in category_seconds(tables)]


def collective_share(tables: dict):
    """Time in collective ops over device busy time, mean over devices."""
    shares = []
    for device, busy in zip(tables["devices"], busy_seconds(tables)):
        if busy > 0:
            hit = sum(s for (name, category), s
                      in _leaf_seconds(device).items()
                      if is_collective(category))
            shares.append(hit / busy)
    return sum(shares) / len(shares) if shares else None


def filing_note(filing: dict, n: int = 8) -> str:
    """One line for the run's notes: how the categories were decided,
    which ops that moved and which names stayed ambiguous."""
    moved = [f"{short}: {was} -> {now}"
             for short, (was, now) in sorted(filing["moved"].items())]
    more = f"; and {len(moved) - n} more" if len(moved) > n else ""
    return (f"op categories by {filing['by']}: {len(moved)} moved"
            + (f" ({'; '.join(moved[:n])}{more})" if moved else "")
            + f", {len(filing['ambiguous'])} ambiguous"
            + (f" ({' '.join(filing['ambiguous'][:n])})"
               if filing["ambiguous"] else ""))


def describe_ops(programs: list) -> dict:
    """``{op name: "part path window path window ..."}`` for the ops the
    programs place or list a convolution for: what is inside an op that
    XLA names and shapes after its root (``fusion.1068
    bf16[20,256,32,9,12]`` is ``dec0/conv3`` with the head as its
    consumer: PERF.md, PR 38). The part only where the programs agree on
    one; the convolutions of the first program that lists any."""
    places = places_of_ops(programs) or {}
    held = convolutions_of_ops(programs) or {}
    out = {}
    for op in set(places) | set(held):
        parts = {part for _, part in places.get(op, ())}
        listed = next((some for some in held.get(op, ()) if some), [])
        words = [parts.pop() if len(parts) == 1 else ""] + [
            f"{path} {window}".strip() for path, window in listed]
        if any(words):
            out[op] = " ".join(filter(None, words))
    return out


def top_ops(tables: dict, n: int = 10, programs: list = ()) -> list:
    """[name, seconds] of innermost-op time, summed over devices and
    divided by their number: the ops that took most time on a chip, each
    with its category and, where ``programs`` say so, its part and the
    convolutions inside it (``fusion.1068 bf16[20,256,32,9,12]
    [convolution] dec0 dec0/conv3 3x3x3 out 1x1x1``)."""
    inside = describe_ops(programs)
    totals: dict = {}
    for device in tables["devices"]:
        for (name, category), seconds in _leaf_seconds(device).items():
            key = " ".join(filter(None, (
                f"{name} [{category}]", inside.get(name.split(" ", 1)[0]))))
            totals[key] = totals.get(key, 0.0) + seconds
    count = max(1, len(tables["devices"]))
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, seconds / count] for name, seconds in ranked]


def idle_gaps(tables: dict, n: int = 10) -> list:
    """The longest idle gaps of the first device, each named after the
    host event of the trace that overlaps it most (the profiler puts both
    on one clock), or `unattributed`; gaps of one name are summed."""
    if not tables["devices"]:
        return []
    busy = _busy(tables["devices"][0])
    edges = [tables["t0_ns"]] + [t for pair in busy for t in pair] \
        + [tables["t1_ns"]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:50]
    named: dict = {}
    for start, end in gaps:
        # the shortest host event that covers at least half of the gap:
        # the most specific thing the host was doing then
        best, best_dur = "unattributed", None
        for name, h_start, h_dur in tables["host"]:
            overlap = min(end, h_start + h_dur) - max(start, h_start)
            if overlap >= 0.5 * (end - start) and (
                    best_dur is None or h_dur < best_dur):
                best, best_dur = name, h_dur
        named[best] = named.get(best, 0) + (end - start)
    ranked = sorted(named.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def breakdown(tables: dict, programs: list = ()) -> dict:
    return {"device_ops": top_ops(tables, programs=programs),
            "idle_gaps": idle_gaps(tables)}


def cut(tables: dict, start_s: float, seconds: float) -> dict:
    """The part of ``tables`` between ``start_s`` and ``start_s + seconds``
    after the trace's first event; events are clipped at the edges. This
    is how a recorded trace is cut to what a test needs."""
    lo = tables["t0_ns"] + int(start_s * 1e9)
    hi = lo + int(seconds * 1e9)

    def clip(start, dur):
        a, b = max(start, lo), min(start + dur, hi)
        return (a, b - a) if b > a else None

    devices = []
    for device in tables["devices"]:
        ops = []
        for name, category, start, dur in device["ops"]:
            kept = clip(start, dur)
            if kept:
                ops.append([name, category, *kept])
        devices.append({"name": device["name"], "ops": ops})
    host = []
    for name, start, dur in tables["host"]:
        kept = clip(start, dur)
        if kept:
            host.append([name, *kept])
    return {"window_s": (hi - lo) / 1e9, "t0_ns": lo, "t1_ns": hi,
            "devices": devices, "host": host}
