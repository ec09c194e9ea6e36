"""Compile caches: what the first calls cost — compilation on a cold
cache, loads from JAX's persistent cache on a warm one."""


def read(run):
    return run.setup.get("first_calls_s")
