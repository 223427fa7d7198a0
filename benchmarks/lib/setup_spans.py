"""The set-up of a run as the program's own spans tell it (PR 36): the
process's start (`rw:boot`) and what jax did for every compile
(`rw:compile` on the compile service's workers, `rw:compile.inline`
wherever else jax compiled: `persistent` = `hit` / `miss` / `off`,
`backend_compile_s`, `retrieval_s`, `lost`), cut to the spans that ended
before the window's first barrier. A program whose spans do not say these
things (a commit before PR 36) reads as nothing: every function gives
`None` and the reader leaves its metric out.
"""
import spans

COMPILE_NAMES = ("rw:compile", "rw:compile.inline")
# a backend compile of at least this long is a program jax built; below
# it an eager primitive (the program's `profile.BUILD_MIN_S` draws the
# same line for its own counter)
BUILD_MIN_S = 1.0


def compiles(p):
    """The set-up's compile spans that say what jax did, or `None` where
    none does."""
    if p is None:
        return None
    out = [s for name in COMPILE_NAMES for s in p.before_window(name)
           if "persistent" in s]
    return out or None


def built(p):
    """Of those, the programs jax built: not read from the persistent
    cache, and a backend compile of `BUILD_MIN_S` or more."""
    found = compiles(p)
    if found is None:
        return None
    return [s for s in found if s["persistent"] != "hit"
            and s.get("backend_compile_s", 0.0) >= BUILD_MIN_S]


def loaded(p):
    found = compiles(p)
    if found is None:
        return None
    return [s for s in found if s["persistent"] == "hit"]


def boot(p):
    """The `rw:boot` span, or `None`."""
    if p is None:
        return None
    found = spans.named(p.spans, "rw:boot")
    return found[0] if found else None
