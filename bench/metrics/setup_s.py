"""Seconds from the start of the process to the start of the window:
imports, inputs and bank made from the seed, compilation (or loading it
from the cache), warm-up."""


def value(rec):
    return rec["setup_s"]
