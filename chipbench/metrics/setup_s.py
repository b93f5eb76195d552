"""Seconds from the start of the process to the end of the warm-up: JAX
start, the payload table, sizing the stores, compiling or loading every
program, and the warm-up traffic."""


def read(rec):
    return rec["setup_s"]
