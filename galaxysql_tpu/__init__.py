"""galaxysql_tpu: a TPU-native distributed SQL engine (PolarDB-X CN capabilities,
re-designed for JAX/XLA — see SURVEY.md for the blueprint)."""

import jax


def _ensure_platforms():
    """Allow a CPU backend beside the accelerator (TP queries run host-side).

    Must run before JAX initializes its backends.  When `jax_platforms`
    (JAX_PLATFORMS) pins accelerator platforms only, extend the list with
    'cpu': the accelerator stays the default backend (first listed) and a
    listed platform that cannot start is still an error, never a fallback."""
    plats = jax.config.jax_platforms
    if plats and "cpu" not in plats.split(","):
        jax.config.update("jax_platforms", plats + ",cpu")


_ensure_platforms()
# Decimal and key lanes are int64, hashing is SplitMix64 in uint64, averages
# are float64: with x64 off the first aggregate truncates or overflows.  Every
# entry point imports this package, so this is the one place that enables it
# (`Instance()` refuses to boot if a caller switched it back off).
jax.config.update("jax_enable_x64", True)
