"""A ``/query`` request's mean server time outside its queue and its batch,
in the cells whose end-to-end metric is a rate: ``request_edge_ms.lat``'s
reading (the phases ``read``, ``admit``, ``wake``, ``encode`` and ``write``
of ``frontend_request_phase_seconds_total{phase,route="query"}`` over
``frontend_request_seconds_count``; that file says how the program measures
them). A 1024 x 10 answer's ``encode`` — a Python list of floats and
``json.dumps``, with the interpreter lock held — is most of it. Source:
program counter."""

from benchmark.harness import load_by_path

read = load_by_path("layer_metrics", "request_edge_ms.lat").read
