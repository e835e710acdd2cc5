import os

# Tests run on the CPU platform with a virtual 8-device mesh; both must be set
# before jax is imported (tier-1 proves correctness and counts, never speed).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Background jit pre-warm of every pow2 bucket is the encoder SERVICE's startup
# behavior; under the test suite it would burn CPU compiling tiny throwaway
# models per embedder construction. Default it off (the pre-warm tests opt back
# in with monkeypatch / explicit ctor args).
os.environ.setdefault("PATHWAY_ENCSVC_PREWARM", "0")

import pytest


@pytest.fixture
def leak_oracle():
    """Dynamic resource-leak oracle — the PWA201 static model proven against
    the live runtime. Snapshots this process's fds (with their targets) and
    threads before the test and fails on growth after it: a leaked socket,
    pipe, file handle, or thread surviving the test is exactly the
    acquire-without-release class the resource lint hunts. A generous settling
    grace absorbs teardown that legitimately takes a moment under full-suite
    load (daemon reapers, GC-driven closes)."""
    import gc
    import threading
    import time

    fd_dir = "/proc/self/fd"

    def fd_snapshot():
        out = {}
        for fd in os.listdir(fd_dir):
            try:
                out[fd] = os.readlink(os.path.join(fd_dir, fd))
            except OSError:
                pass  # raced a close (or the listdir fd itself)
        return out

    before_fds = fd_snapshot()
    before_threads = {t.ident for t in threading.enumerate()}
    yield
    deadline = time.monotonic() + 60
    while True:
        gc.collect()
        after_fds = fd_snapshot()
        new_threads = [
            t
            for t in threading.enumerate()
            if t.ident not in before_threads and t.is_alive()
        ]
        fd_growth = len(after_fds) - len(before_fds)
        new_sockets = [
            target
            for fd, target in after_fds.items()
            if fd not in before_fds and "socket" in target
        ]
        if fd_growth <= 0 and not new_threads and not new_sockets:
            break
        if time.monotonic() > deadline:
            raise AssertionError(
                "leak oracle: resources grew across the test — "
                f"fd growth {fd_growth} (new sockets: {new_sockets}), "
                f"leaked threads: {[t.name for t in new_threads]}"
            )
        time.sleep(0.5)


@pytest.fixture(autouse=True)
def clear_graph():
    """Each test gets a fresh global parse graph."""
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    yield
    G.clear()


@pytest.fixture(autouse=True)
def clear_brownout():
    """The brownout ladder is a process-wide singleton fed by admission
    probes; a shed test saturating one encoder service must not leave a rung
    engaged (tightened caps, halved probes) for the next test."""
    from pathway_tpu.engine.brownout import reset_brownout

    reset_brownout()
    yield
    reset_brownout()
