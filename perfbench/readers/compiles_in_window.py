"""Backend compilations (or loads from the compile cache of a program
new to the process) that JAX reported between the window's opening and
its close. Should be 0: every shape is warmed in set-up."""


def read(run):
    return float(run.compiles_in_window())
