"""Device-busy milliseconds per train step of the exit gates, the exit distribution, its entropy and the blend of the exits' losses (scope `head_loss/exit_gate`): what the exits cost beyond their
heads (those are `head_loss/exit_<s>`, inside `head_loss_ms.train`),
forward and backward together, the median over the traced steps
(layer_metrics/_ouro.py)."""

from benchmark.layer_metrics import _ouro


def read(run: dict):
    got = _ouro.parts()
    return None if got is None else got[_ouro.GATE]
