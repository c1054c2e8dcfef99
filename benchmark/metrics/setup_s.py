"""Seconds from process start to the start of the window: JAX and device
initialisation, the native build if absent, server start, clients,
handshake, warm-up."""


def read(data):
    return data["setup_s"]
