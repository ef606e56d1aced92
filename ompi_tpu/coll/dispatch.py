"""coll/dispatch — the one seam a device collective is launched and
observed through.

A blocking device slot of coll/xla, coll/hier, coll/pallas or
coll/xla_neighbor keeps what is its own — its gates (staging
fallthrough, size 1, count errors) and its prep (plan, compile, bind
the operands) — and hands the prepared zero-argument launcher to
:func:`run`. The three observation planes (``monitoring.matrix.
TRAFFIC``, ``tune.observe.OBSERVER``, ``telemetry.flight.FLIGHT``)
keep their handles and their APIs; this module owns WHO reads them
under ``ompi_tpu/coll/``, in what order, once per API call. A hier or
pallas slot that falls through never gets here; the xla slot it calls
does, so a call is observed once, under the provider that served.

With all three planes down — the default, and every benchmark run —
:func:`run` costs the provider's counter and three guard reads,
constructs nothing, and reads neither the operand's ``nbytes`` nor
its ``dtype`` (microseconds each on a jax array). The span
``ompi:coll_xla.<op>`` opens AROUND gate + prep + launch
(``coll/xla._slot``); this function's time is that span's self time.
"""

from __future__ import annotations

from ompi_tpu.core import pvar
from ompi_tpu.monitoring import matrix as _mon
from ompi_tpu.telemetry import flight as _flight
from ompi_tpu.tune import observe as _tobs

#: the always-on counter of calls a provider served on the device
_CALLS = {"xla": "coll_xla_device", "hier": "hier_launches",
          "pallas": "pallas_launches"}


def nbytes_of(buf) -> int:
    """Operand bytes of a slot: an array's or a ShardedState's own
    ``nbytes``, else the sum over a pytree's leaves (None: 0)."""
    nb = getattr(buf, "nbytes", None)
    if nb is None:
        import jax

        nb = sum(getattr(leaf, "nbytes", 0)
                 for leaf in jax.tree.leaves(buf))
    return int(nb)


def run(provider: str, op: str, comm, buf, launcher, *,
        nbytes=None, dtype=None, algorithm=None, mesh=None, kind=None,
        root: int = 0, counts=None, per_peer=None, ctx: str = "coll"):
    """Run ``launcher()`` — one device collective ``op`` of ``comm``
    served by ``provider`` on operand ``buf`` — and return its result:
    traffic accounted on entry, the launcher timed for tune (dispatch
    time only), a flight entry ``<op>_dev`` (the name watchdog dumps,
    ``skew/`` and the ``trace_hist_*`` families are keyed by) opened
    before the launch and closed in ``finally``.

    ``nbytes`` / ``dtype`` override what is read off ``buf`` (a fused
    bucket, a pytree). ``algorithm`` (None: 'auto') and ``mesh``
    (None: the comm's own) key the tune sample. The rest is what
    ``TrafficMatrix.coll`` takes: ``kind`` the traffic model's name
    for the op where it differs, ``counts`` the per-peer rows of
    ``buf`` (a row's bytes: ``buf``'s over its dim 0), ``per_peer``
    the explicit send-side dict or a zero-argument callable building
    it (called only with monitoring up), ``root`` and ``ctx``."""
    pvar.record(_CALLS[provider])
    tm = _mon.TRAFFIC
    obs = _tobs.OBSERVER
    fl = _flight.FLIGHT
    if tm is None and obs is None and fl is None:
        return launcher()
    if nbytes is None:
        nbytes = nbytes_of(buf)
    dtype = str(getattr(buf, "dtype", "") if dtype is None else dtype)
    if tm is not None:
        if callable(per_peer):
            per_peer = per_peer()
        tm.coll(kind or op, comm, nbytes, dtype=dtype, root=root,
                per_peer=per_peer, counts=counts, ctx=ctx,
                row_bytes=nbytes / buf.shape[0]
                if counts is not None and nbytes else 0.0)
    if obs is not None:
        launcher = obs.timed(provider, op, algorithm or "auto", comm,
                             nbytes, dtype, launcher, mesh=mesh)
    if fl is None:
        return launcher()
    tok = fl.enter(op + "_dev", getattr(comm, "cid", -1), nbytes)
    try:
        return launcher()
    finally:
        fl.exit(tok)


def cycle_enter(name: str, comm, nbytes: int):
    """Flight entry of a partitioned cycle, which spans calls
    (``start()`` .. ``wait()``) and so is no launcher of :func:`run`:
    the token for :func:`cycle_exit`, None with the recorder down."""
    fl = _flight.FLIGHT
    if fl is None:
        return None
    return fl.enter(name, getattr(comm, "cid", -1), nbytes)


def cycle_exit(token) -> None:
    fl = _flight.FLIGHT
    if token is not None and fl is not None:
        fl.exit(token)
