"""scripts/compile_cell.py's reading of a compiled step's text: which
copies count as pathless (tests/ holds no chip and compiles nothing
here; the script's compile is a scratch run, ~1 min a cell)."""

import importlib.util
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "compile_cell", os.path.join(HERE, "scripts", "compile_cell.py"))
compile_cell = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(compile_cell)

TEXT = """HloModule jit_step

%fused_computation.1 (p: bf16[1,16384,2304]) -> bf16[1,16384,2304] {
  %copy.9 = bf16[1,16384,2304]{2,1,0:T(8,128)(2,1)} copy(%p)
}

ENTRY %main.1 (a: bf16[131072,2304]) -> bf16[16384,2304] {
  %copy.1 = bf16[1,16384,2304]{2,1,0:T(8,128)(2,1)} copy(%slice.1)
  %copy-start.2 = (bf16[1,16384,2304]{2,1,0}, bf16[1,16384,2304]{2,1,0}, u32[]) copy-start(%copy.1)
  %copy.3 = f32[8192,2688]{1,0:T(8,128)} copy(%x), metadata={op_name="jit(step)/layer_0/mlp/moe_combine/reduce_sum"}
  %copy.4 = s32[8,16384]{1,0:T(8,128)} copy(%at)
  ROOT %copy.5 = f32[16384,2304]{1,0:T(8,128)} copy(%y)
}
"""


def test_pathless_copies_are_the_entrys_large_copies_without_an_op_path():
    """Counted: the entry's `copy` and root copy of 32 MB and more with
    no `op_name`; not counted: a copy inside a fusion, a prefetch
    (`copy-start`), one with a scope path, a small one."""
    slab, whole = 16384 * 2304 * 2, 16384 * 2304 * 4
    assert compile_cell.pathless_copies(TEXT) == (2, slab + whole)
    assert compile_cell.pathless_copies(TEXT, least=1) == (
        3, slab + whole + 8 * 16384 * 4)
    assert compile_cell.pathless_copies(TEXT, least=whole + 1) == (0, 0)
