#!/usr/bin/env python
"""Say whether two dumps of ``tools/offline_step.py --hlo-dir`` are one program.

An edit to a file a cell traces moves source lines, and a body that moves into
another function adds a call frame. The compiled step's text carries both: in
its four tables (``FileNames``, ``FunctionNames``, ``FileLocations``,
``StackFrames``, which change length with a frame), in every op's
``stack_frame_id=N`` into them, and inside each Pallas kernel's serialized MLIR
(``"body":"<base64>"``). So the texts of a parent and a change that compile the
same program still differ. This compares them with all of that taken out: the
tables dropped, the frame ids blanked, every other line equal, and every kernel
body equal once it is printed without its source locations::

    JAX_PLATFORMS=cpu python tools/same_step.py /root/scratch/hlo_parent /root/scratch/hlo_change

Both dumps have to come from ONE directory (unpack parent and change there in
turn): the checkout's path is inside the kernels' serialized MLIR. Exit code 1 if
any cell differs.
"""

import base64
import os
import re
import sys

_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_FRAME_ID = re.compile(r"stack_frame_id=\d+")
_BODY = re.compile(r'"body":"([^"]+)"')


def _kernel_text(body: str) -> str:
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    with mlir.make_ir_context() as ctx:
        ctx.allow_unregistered_dialects = True      # ``stable_mosaic``: printed, not verified
        module = ir.Module.parse(base64.b64decode(body))
        return module.operation.get_asm(enable_debug_info=False)


def program_lines(text: str) -> list:
    """The lines of a compiled module's text that say what runs: the source
    tables (a heading, its rows, up to the blank line after) dropped, and each
    op's ``stack_frame_id`` blanked."""
    out, in_table = [], False
    for line in text.splitlines():
        if in_table:
            in_table = bool(line.strip())
        elif line in _TABLES:
            in_table = True
        else:
            out.append(_FRAME_ID.sub("stack_frame_id=", line))
    return out


def differing(parent: str, change: str) -> list:
    """Where the two texts are not one program: numbers into :func:`program_lines`
    (1-based), or one sentence if the two have different numbers of lines."""
    a, b = (program_lines(open(path).read()) for path in (parent, change))
    if len(a) != len(b):
        return [f"{len(a)} lines against {len(b)}"]
    out = []
    for n, (x, y) in enumerate(zip(a, b), 1):
        if x == y:
            continue
        bx, by = _BODY.search(x), _BODY.search(y)
        if not (bx and by and _BODY.sub("", x) == _BODY.sub("", y)
                and _kernel_text(bx.group(1)) == _kernel_text(by.group(1))):
            out.append(n)
    return out


def main(argv=None) -> int:
    parent, change = (argv or sys.argv[1:])[:2]
    worst = 0
    for name in sorted(os.listdir(parent)):
        if not name.endswith(".hlo.txt"):
            continue
        lines = differing(os.path.join(parent, name), os.path.join(change, name))
        print(f"{name[:-len('.hlo.txt')]}: "
              + ("the same program" if not lines else f"differs at lines {lines[:8]}"))
        worst |= bool(lines)
    return worst


if __name__ == "__main__":
    raise SystemExit(main())
