"""Wall seconds the program's commit tuner spent calibrating and racing
in this run, set-up included (``AutoTuner.tune_s``: its ``aam.tune``
spans, compiles of the micro-commits included)."""


def read(ctx):
    from repro.core.autotune import DEFAULT_TUNER
    return getattr(DEFAULT_TUNER, "tune_s", None)
