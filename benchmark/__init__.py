"""The benchmark. What belongs to one model family, one optimizer, one
driver, one assignment rule or one metric is a file of its own in the
directory of its kind, found by the name a data file gives."""

import importlib
import os


def named(group: str, name: str):
    """The module ``benchmark/<group>/<name>.py``. A name with no file is a
    KeyError that lists the names there are."""
    module = f"benchmark.{group}.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)), group)
    there = sorted(f[:-3] for f in os.listdir(here)
                   if f.endswith(".py") and not f.startswith("_"))
    raise KeyError(f"no benchmark/{group}/{name}.py; {group} there are: "
                   f"{there}")


def family_of(arch: dict):
    """The file of the model family that a configuration's ``arch`` names."""
    return named("families", arch["family"])
