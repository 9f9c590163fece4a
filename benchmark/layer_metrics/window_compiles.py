"""Programs compiled or loaded from jax's persistent cache during the
window, whoever asked for them: ``jax_compiles_total`` plus
``jax_cache_loads_total`` (both fed by the program's ``jax.monitoring``
listener), as the difference of the two ``/metrics`` reads around the
window. At jax 0.9.0 a load fires the compile event too, so a load counts
twice; a warm window reads 0. The check's ``compiled_in_window`` sees the
serve cache's own executables only. Source: program counter."""


def read(run: dict):
    delta = run.get("window_metrics_delta")
    if not delta or "jax_compiles_total" not in delta:
        return None
    return (delta["jax_compiles_total"]
            + delta.get("jax_cache_loads_total", 0.0))
