"""The control of a cell's comparison: a run in the precision below the
one the configuration states, which the comparison has to fail.

A cell's limits file names its control as ``"program:<dtype>"``: the
program itself with its own storage precision switched to ``dtype``.
"""
from __future__ import annotations


def program_dtype(control: str) -> str:
    """The storage precision the control runs the program in."""
    kind, _, dtype = control.partition(":")
    if kind != "program" or not dtype:
        raise ValueError(f"unknown control {control!r}")
    return dtype
