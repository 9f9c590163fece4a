"""The child that holds the chip in a cosine serving cell:
``serve_launcher.py``'s own ``main`` — the same corpus, build, warm-up,
server, signals and ``final.json`` — with two differences.

- The reference it calls is ``reference_cosine.exact_knn_cosine``.
  ``serve_launcher.main`` calls ``reference.exact_knn`` by name, so this
  file puts the cosine reference under that name, in this process only,
  before it calls ``main`` (folding the two launchers into one that reads
  the reference from the configuration is a ``benchmark`` issue's: this PR
  may edit no file that is there).
- A traced run hands on the kernels' scopes: after ``main`` has written
  ``final.json`` this adds ``"scopes"`` to it, own device seconds inside the
  traced span by the program's innermost ``knn.*`` scope
  (``drivers/allknn_ring.py ring_scopes``, through the program's
  ``obs/xplane.py parse_xplane``); None where the trace names none.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def cosine_reference(corpus, queries, k, exclude_zero=False):
    """``reference.exact_knn``'s call, answered by the cosine reference."""
    from benchmark import reference_cosine

    if exclude_zero:
        raise SystemExit("error: the cosine reference leaves no row out; "
                         "the configuration says exclude_zero")
    return reference_cosine.exact_knn_cosine(corpus, queries, k)


def traced_scopes(run_dir: str):
    """``[[scope, seconds], ...]`` of the run's trace, largest first, or
    None (no trace, no window annotation, no scope names in it)."""
    from benchmark import harness, trace

    xplane = trace.newest_xplane(os.path.join(run_dir, "trace"))
    if xplane is None:
        return None
    spans = [(s, s + d) for n, s, d in trace.read_xplane(xplane)["host"]
             if n == trace.WINDOW_ANNOTATION]
    if not spans:
        return None
    ring = harness.load_by_path("drivers", "allknn_ring")
    return ring.ring_scopes(xplane, min(s for s, _ in spans),
                            max(e for _, e in spans))


def main(argv=None) -> int:
    sys.path[:] = [ROOT] + [d for d in sys.path if d != ROOT]  # first
    from benchmark import reference, serve_launcher

    reference.exact_knn = cosine_reference
    rc = serve_launcher.main(argv)
    args = sys.argv[1:] if argv is None else list(argv)
    run_dir = args[args.index("--run-dir") + 1]
    final_path = os.path.join(run_dir, "final.json")
    if rc == 0 and os.path.exists(final_path):
        with open(final_path) as f:
            final = json.load(f)
        final["scopes"] = traced_scopes(run_dir)
        serve_launcher.write_json(final_path, final)
    return rc


if __name__ == "__main__":
    sys.exit(main())
