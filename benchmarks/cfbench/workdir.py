"""Where a run keeps its work directory: the input volume, the output
volume, the queue, the program's metrics and the profiler's trace.

A traffic file that gives ``work_gb`` (what the cell holds at once) has
it on a memory-backed file system: ``/dev/shm/cfbench-<checkout>/<cell>-
<pid>``, if ``/dev/shm`` is a tmpfs with 1.5 times ``work_gb`` free.
Where it is not, the run ends with no result: the same cell on the
machine's disk would be another measurement under the same name (PERF.md,
PR 35: the disk of the measuring machine throttled a worker that writes
0.47 GB/s). A traffic file without the key, and every rehearsal, keeps it
in the checkout under ``benchmarks/.work``.

``<checkout>`` is a hash of the checkout's real path, so two checkouts on
one machine (a parent and a change) share no name under ``/dev/shm``. A
run that was killed leaves its directory behind, and on a tmpfs that is
memory the next run needs: ``place`` first removes, inside its own root
and nowhere else, every directory that this checkout signed
(``.owner``: the checkout's path and the machine's boot id) and whose
process is gone.
"""
import hashlib
import os
import shutil

SHM = "/dev/shm"
HEADROOM = 1.5
OWNER = ".owner"


def is_tmpfs(path: str, mounts: str = "/proc/mounts") -> bool:
    try:
        with open(mounts) as f:
            rows = [line.split() for line in f]
    except OSError:
        return False
    return any(len(row) >= 3 and row[1] == path and row[2] == "tmpfs"
               for row in rows)


def free_bytes(path: str, meminfo: str = "/proc/meminfo") -> int:
    """What a tmpfs at ``path`` can still take: its own free space or the
    machine's available memory, whichever is less (a tmpfs may be sized
    past the memory behind it: 95 GB on 47 on the chip's host, PR 35)."""
    stat = os.statvfs(path)
    free = stat.f_bavail * stat.f_frsize
    try:
        with open(meminfo) as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    free = min(free, int(line.split()[1]) * 1024)
    except OSError:
        pass
    return free


def owner(checkout_work: str) -> str:
    """What a directory of this checkout, made since this boot, is signed
    with."""
    try:
        with open("/proc/sys/kernel/random/boot_id") as f:
            boot_id = f.read().strip()
    except OSError:
        boot_id = ""
    return f"{os.path.realpath(checkout_work)}\n{boot_id}\n"


def root_of(checkout_work: str, shm: str = SHM) -> str:
    digest = hashlib.sha256(os.path.realpath(checkout_work).encode())
    return os.path.join(shm, "cfbench-" + digest.hexdigest()[:12])


def alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:      # somebody else's, and there
        pass
    return True


def sweep(root: str, signed: str) -> list:
    """Remove ``<root>/<cell>-<pid>`` where it carries this checkout's
    signature and the pid is gone (or the signature is of another boot:
    the pid then is nobody's); the names removed. A directory with no
    signature, another checkout's, or a name that ends in no pid is not
    this checkout's to remove."""
    removed = []
    try:
        names = os.listdir(root)
    except FileNotFoundError:
        return removed
    checkout = signed.split("\n")[0]
    for name in names:
        pid = name.rpartition("-")[2]
        try:
            with open(os.path.join(root, name, OWNER)) as f:
                theirs = f.read()
        except OSError:
            continue
        if not pid.isdigit() or theirs.split("\n")[0] != checkout:
            continue
        if theirs != signed or not alive(int(pid)):
            shutil.rmtree(os.path.join(root, name), ignore_errors=True)
            removed.append(name)
    return removed


def place(traffic: dict, cell: str, rehearse: bool, checkout_work: str,
          shm: str = SHM) -> tuple:
    """(the run's work directory, a note that says where it lies and
    why). Ends the process where the traffic asks for memory and the
    machine has none to give."""
    name = f"{cell}-{os.getpid()}"
    if "work_gb" not in traffic or rehearse:
        why = ("a rehearsal" if rehearse
               else "the traffic file gives no work_gb")
        return os.path.join(checkout_work, name), \
            f"work directory in the checkout ({why})"
    need = HEADROOM * float(traffic["work_gb"]) * 1e9
    if not is_tmpfs(shm):
        raise SystemExit(
            f"benchmarks: the cell keeps its volumes in memory and {shm} "
            f"is no tmpfs on this machine. On the disk it would be "
            f"another measurement. No result.")
    root = root_of(checkout_work, shm)
    work = os.path.join(root, name)
    swept = sweep(root, owner(checkout_work))
    free = free_bytes(shm)
    if free < need:
        raise SystemExit(
            f"benchmarks: the cell keeps {traffic['work_gb']} GB of "
            f"volumes in memory and wants {need / 1e9:.1f} GB free on "
            f"{shm}; it has {free / 1e9:.1f} GB. On the disk it would be "
            f"another measurement. No result.")
    note = (f"work directory {work}: tmpfs, "
            f"{free / 1e9:.1f} GB free, {traffic['work_gb']} GB wanted "
            f"x {HEADROOM}")
    if swept:
        note += f"; removed what dead runs left: {' '.join(swept)}"
    return work, note


def create(work: str, checkout_work: str) -> None:
    """Make the directory anew and sign it."""
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    with open(os.path.join(work, OWNER), "w") as f:
        f.write(owner(checkout_work))


def remove(work: str) -> None:
    """The directory and, once it holds no other run's, its root."""
    shutil.rmtree(work, ignore_errors=True)
    try:
        os.rmdir(os.path.dirname(work))
    except OSError:
        pass
