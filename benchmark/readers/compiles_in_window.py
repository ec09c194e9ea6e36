"""Compile caches: compile requests inside the window (expected 0).

jax.monitoring's count and the profiles' own have to agree on whether
anything compiled; where they do not, the larger is reported."""


def read(run):
    by_profiles = sum(st.profile.compiled_programs for st in run.statements
                      if st.profile is not None)
    return max(int(run.compiles_in_window), int(by_profiles))
