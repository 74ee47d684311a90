"""Blocking device-to-host fetches per answer: the `fetch.get` counter
over the window, divided by the answers in it."""


def read(r):
    return r.per_unit_counter("fetch.get")
