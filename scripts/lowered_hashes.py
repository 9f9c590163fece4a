#!/usr/bin/env python
"""Hash the lowered program of every lint cell of a checkout, to compare
two commits: which cells' programs a change touched, and which it left byte
for byte as they were.

    python scripts/lowered_hashes.py <checkout> <out.json> [<dir for the texts>]
    python scripts/lowered_hashes.py --diff <a.json> <b.json>

The text hashed is the StableHLO of ``jax.stages.Lowered.as_text()``: it
holds no source locations, so an edit that moves lines changes nothing. The
cells are ``analysis.lowering.default_targets()``, lowered as the lint
lowers them, on eight virtual CPU devices; the checkout is put first on
``sys.path``, so run it once per checkout (a parent unpacked by ``git
archive`` and the working tree), then ``--diff`` the two files.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys


def hashes(root: str, texts_dir: str | None) -> dict:
    sys.path.insert(0, os.path.abspath(root))
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    from mpi_knn_tpu.analysis import lowering

    out = {}
    for t in lowering.default_targets():
        try:
            if t.mutate:
                lowered = lowering._lower_mutate(t)[0]
            elif t.serve:
                lowered = lowering._lower_serve(t)[0]
            else:
                lowered = lowering._LOWERERS[t.backend](t)[0]
        except lowering.UnsupportedTarget:
            out[t.label] = "unsupported"  # float64 without x64, and the like
            continue
        text = lowered.as_text()
        out[t.label] = hashlib.sha256(text.encode()).hexdigest()
        if texts_dir:
            os.makedirs(texts_dir, exist_ok=True)
            name = t.label.replace("/", "_") + ".mlir"
            with open(os.path.join(texts_dir, name), "w") as f:
                f.write(text)
    return out


def diff(a_path: str, b_path: str) -> int:
    with open(a_path) as f:
        a = json.load(f)
    with open(b_path) as f:
        b = json.load(f)
    changed = sorted(k for k in a.keys() | b.keys() if a.get(k) != b.get(k))
    print(f"{len(a.keys() & b.keys()) - len(changed)} cells equal, "
          f"{len(changed)} differ")
    for k in changed:
        print(f"  {k}")
    return 1 if changed else 0


def main(argv: list[str]) -> int:
    if len(argv) == 3 and argv[0] == "--diff":
        return diff(argv[1], argv[2])
    if len(argv) not in (2, 3):
        print(__doc__, file=sys.stderr)
        return 2
    out = hashes(argv[0], argv[2] if len(argv) == 3 else None)
    with open(argv[1], "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(f"{len(out)} cells -> {argv[1]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
