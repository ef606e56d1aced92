"""What a lowered step's text says, apart from the residuals' names
(PR 35).

The names (`jax.ad_checkpoint.checkpoint_name` in models/transformer.py,
models/vision.py, ops/attention.py and ops/ssm.py; models/remat.py owns
the models' names and calls it nowhere) lower to their operands and
leave no operation.
But jax lowers EVERY equation through a private function named after
its primitive (inlined again at once), and MLIR's symbol table tells
two of a name apart by a counter the whole module shares: one more
equation anywhere moves the suffix of every function that stays
(``@_where_113`` becomes ``@_where_115``). So the sha256 pins of the
parents' texts (tests/test_glm5.py, tests/test_ouro.py) are held
against the text lowered `without_names` — raw, as recorded — and the
text with the names is held to be that one with its private functions
numbered by first appearance (`canonical`)."""

import hashlib
import re

from ompi_tpu.models import transformer as tfm
from ompi_tpu.models import vision
from ompi_tpu.ops import attention as att
from ompi_tpu.ops import ssm

_NUMBERED = re.compile(r"@([A-Za-z_][A-Za-z_0-9]*?)_(\d+)\b")


def without_names(monkeypatch):
    """From here on in this test `checkpoint_name` is the identity in
    every module that names residuals."""
    for module in (tfm, vision, att, ssm):
        monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)


def canonical(text: str) -> str:
    seen = {}

    def renumber(found):
        return seen.setdefault(found.group(0),
                               f"@{found.group(1)}_{len(seen)}")

    return _NUMBERED.sub(renumber, text)


def sha256(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()
