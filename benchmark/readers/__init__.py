"""Readers: each takes one kind of number from a run's observations.  A
metric's declaration (``benchmark/metrics/<name>.json``) names one."""
