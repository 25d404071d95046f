"""driver.window_compiles: XLA backend compiles inside the measured
window (JAX's ``backend_compile_duration`` events). Set-up compiles or
loads every program the window runs, so this reads 0."""


def read(run):
    return run.window_compiles
