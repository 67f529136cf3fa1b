"""Step programs: mean engine step, on the engine's clock (delta of
``serving_step_duration_seconds`` sum over count)."""
import readers


def reduce(src):
    return readers.ratio_ms(src, "serving_step_duration_seconds")
