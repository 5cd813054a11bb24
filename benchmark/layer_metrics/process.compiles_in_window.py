"""Compile requests between the window's opening and closing. Must be 0:
every shape the window uses was warmed in set-up."""


def read(run):
    return run.compiles_window["requests"]
