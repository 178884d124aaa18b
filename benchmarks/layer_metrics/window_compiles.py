"""Drivers: ``jax.monitoring`` backend-compile events between the window's
start and end (a persistent-cache hit counts too). Predicted 0."""


def read(metric, trace, window, ctx):
    return float(window["compiles"])
