"""The tiny configurations the CPU tests run, found as files: every
``data/tiny-<name>.json``, served as the program architecture its
``program.arch`` names. A configuration joins the reference, fault and
control tests by its file alone."""
import glob
import json
import os

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def tiny_configs():
    """``(name, arch)`` of every tiny configuration, by name; ``arch`` is
    None where the file names none, which
    ``test_every_tiny_configuration_resolves`` fails by name."""
    out = []
    for path in sorted(glob.glob(os.path.join(DATA, "tiny-*.json"))):
        try:
            with open(path) as f:
                arch = json.load(f)["program"]["arch"]
        except (ValueError, KeyError, TypeError):
            arch = None
        out.append((os.path.basename(path)[:-len(".json")], arch))
    return out
