"""Compile cache: executables the program lowered during the window."""
from bench import scopes


def read(run):
    return scopes.read(run, scopes.compiles_in_window)
