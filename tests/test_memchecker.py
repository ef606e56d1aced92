"""memchecker — buffer-definedness shadow tracking (check/memchecker).

Reference parity: the MEMCHECKER() annotations in the API layer
(ompi/mpi/c/allreduce.c:52-66) that flag use of undefined receive
buffers under Valgrind; here the shadow map is first-party."""

import numpy as np
import pytest

from ompi_tpu.check import memchecker
from ompi_tpu.core import cvar
from tests import harness


@pytest.fixture(autouse=True)
def _on():
    old = cvar.get("memchecker")
    cvar.set("memchecker", "on")
    memchecker.reset_for_testing()
    yield
    cvar.set("memchecker", old)
    memchecker.reset_for_testing()


def test_send_from_pending_recv_buffer_flagged():
    buf = np.zeros(16, np.float32)
    memchecker.mark_undefined(1, buf)
    with pytest.raises(memchecker.MemcheckError, match="pending"):
        memchecker.check_defined(buf, "send")


def test_defined_after_completion():
    buf = np.zeros(16, np.float32)
    memchecker.mark_undefined(1, buf)
    memchecker.mark_defined(1)
    memchecker.check_defined(buf, "send")  # no raise


def test_overlapping_receives_flagged():
    buf = np.zeros(32, np.float32)
    memchecker.mark_undefined(1, buf[:20])
    with pytest.raises(memchecker.MemcheckError, match="overlap"):
        memchecker.mark_undefined(2, buf[8:])


def test_disjoint_buffers_ok():
    buf = np.zeros(32, np.float32)
    memchecker.mark_undefined(1, buf[:16])
    memchecker.mark_undefined(2, buf[16:])
    memchecker.check_defined(np.zeros(4), "send")  # unrelated: ok


def test_warn_mode_does_not_raise(pvar_clean):
    from ompi_tpu.core import pvar

    cvar.set("memchecker", "warn")
    buf = np.zeros(8, np.float32)
    memchecker.mark_undefined(1, buf)
    memchecker.check_defined(buf, "send")
    assert pvar.read("memchecker_violations") == 1


def test_off_mode_is_noop():
    cvar.set("memchecker", "off")
    buf = np.zeros(8, np.float32)
    memchecker.mark_undefined(1, buf)
    memchecker.check_defined(buf, "send")
    assert not memchecker._undefined


def test_pml_flags_send_from_inflight_recv_buffer():
    """End-to-end: rank 0 posts Irecv into buf then Sends from the same
    buf — the ob1 send entry must flag it (the exact race the
    reference's MEMCHECKER annotations exist for)."""
    harness.run_ranks("""
        from ompi_tpu.check import memchecker
        buf = np.zeros(64, np.float32)
        if rank == 0:
            req = comm.Irecv(buf, source=1, tag=7)
            try:
                comm.Send(buf, 1, tag=9)
                raise SystemExit("memchecker did not flag the race")
            except memchecker.MemcheckError:
                pass
            comm.Send(np.ones(64, np.float32), 1, tag=9)
            req.wait()
            assert buf[0] == 5.0
            # after completion the same buffer sends cleanly
            comm.Send(buf, 1, tag=11)
        else:
            got = np.zeros(64, np.float32)
            comm.Recv(got, 0, tag=9)
            comm.Send(np.full(64, 5.0, np.float32), 0, tag=7)
            comm.Recv(got, 0, tag=11)
            assert got[0] == 5.0
    """, 2, mca={"memchecker": "on"})


def test_pml_clean_run_unflagged():
    harness.run_ranks("""
        a = np.full(32, float(rank), np.float32)
        b = np.zeros(32, np.float32)
        comm.Allreduce(a, b)
        assert b[0] == sum(range(size))
    """, 2, mca={"memchecker": "on"})
