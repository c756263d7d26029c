#!/usr/bin/env python
"""Say whether two dumps of ``tools/offline_step.py --hlo-dir`` are one program.

An edit to a file a cell traces moves source lines, and the compiled step's text
carries them twice: in the frame table (``N {file_name_id=… line=…}``) and inside
each Pallas kernel's serialized MLIR (``"body":"<base64>"``). So the texts of a
parent and a change that compile the same program still differ. This compares
them with both taken out: every other line must be equal, and every kernel body
equal once it is printed without its source locations::

    JAX_PLATFORMS=cpu python tools/same_step.py /root/scratch/hlo_parent /root/scratch/hlo_change

Both dumps have to come from ONE directory (unpack parent and change there in
turn): the checkout's path is in the file table. Exit code 1 if any cell differs.
"""

import base64
import os
import re
import sys

_FRAME = re.compile(r"^\d+ \{file_name_id=\d+ function_name_id=\d+ line=\d+ end_line=\d+ "
                    r"column=\d+ end_column=\d+\}$")
_BODY = re.compile(r'"body":"([^"]+)"')


def _kernel_text(body: str) -> str:
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    with mlir.make_ir_context() as ctx:
        ctx.allow_unregistered_dialects = True      # ``stable_mosaic``: printed, not verified
        module = ir.Module.parse(base64.b64decode(body))
        return module.operation.get_asm(enable_debug_info=False)


def differing(parent: str, change: str) -> list:
    """Lines (1-based) at which the two texts are not one program."""
    a, b = open(parent).read().splitlines(), open(change).read().splitlines()
    if len(a) != len(b):
        return [f"{len(a)} lines against {len(b)}"]
    out = []
    for n, (x, y) in enumerate(zip(a, b), 1):
        if x == y or (_FRAME.match(x) and _FRAME.match(y)):
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
