"""Set-up: seconds JAX spent obtaining executables (compiling, or
fetching from the persistent cache) before the window opened."""


def read(run):
    return {name: run.setup_compile_s for name in run.wanted
            if name == "setup_compile_s"}
