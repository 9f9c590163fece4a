"""What PJRT says of an executable it has already compiled."""

from __future__ import annotations


def pjrt_memory_stats(compiled) -> dict | None:
    """The buffer assignment of one already-compiled executable (zero
    extra compiles, zero device reads): the serving engine's peak-HBM
    figure and the PJRT side of the memory lint's cross-check
    (``analysis.memory``). ``None`` when the runtime cannot answer —
    absent, never fake zeros."""
    try:
        ma = compiled.memory_analysis()
        return {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "peak_bytes": int(
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes
            ),
        }
    except Exception:  # pragma: no cover - runtime-dependent
        return None
