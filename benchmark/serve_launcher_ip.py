"""The child that holds the chip in an inner-product serving cell:
``serve_launcher.py``'s own ``main`` — the same corpus, build, warm-up,
server, signals and ``final.json`` — with the two differences
``serve_launcher_cos.py`` has.

- The reference it calls is ``reference_ip.exact_knn_ip``.
  ``serve_launcher.main`` calls ``reference.exact_knn`` by name, so this
  file puts the inner-product reference under that name, in this process
  only, before it calls ``main`` (one launcher that reads its reference
  from the configuration is a ``benchmark`` issue's: this PR may edit no
  file that is there).
- A traced run hands on the kernels' scopes: after ``main`` has written
  ``final.json`` this adds ``"scopes"`` to it
  (``serve_launcher_cos.traced_scopes``: own device seconds inside the
  traced span by the program's innermost ``knn.*`` scope); None where the
  trace names none.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def ip_reference(corpus, queries, k, exclude_zero=False):
    """``reference.exact_knn``'s call, answered by the inner-product
    reference."""
    from benchmark import reference_ip

    if exclude_zero:
        raise SystemExit("error: a score has no zero that means the same "
                         "row; the configuration says exclude_zero")
    return reference_ip.exact_knn_ip(corpus, queries, k)


def main(argv=None) -> int:
    sys.path[:] = [ROOT] + [d for d in sys.path if d != ROOT]  # first
    from benchmark import reference, serve_launcher, serve_launcher_cos

    reference.exact_knn = ip_reference
    rc = serve_launcher.main(argv)
    args = sys.argv[1:] if argv is None else list(argv)
    run_dir = args[args.index("--run-dir") + 1]
    final_path = os.path.join(run_dir, "final.json")
    if rc == 0 and os.path.exists(final_path):
        with open(final_path) as f:
            final = json.load(f)
        final["scopes"] = serve_launcher_cos.traced_scopes(run_dir)
        serve_launcher.write_json(final_path, final)
    return rc


if __name__ == "__main__":
    sys.exit(main())
