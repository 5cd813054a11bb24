"""``docs/ENV_VARS.md`` and the package name the same options: every
``MXNET_*`` variable the package's sources name has a row, and every row
names a variable the sources still name. Held by ``grep``, as a reader would
check it: a variable that leaves the code takes its row along, and one that
arrives brings one."""
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"MXNET_[A-Z0-9_]+")
# the doc's last table lists the reference's knobs that were NOT carried over
RETIRED = "## Retired reference knobs"


def _package_names():
    names = set()
    for dirpath, _, files in os.walk(os.path.join(ROOT, "mxnet_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name)) as f:
                    names.update(NAME.findall(f.read()))
    return names


def _doc_rows():
    """(the names a live table row mentions anywhere, the names a live row
    is FOR: its first cell's)."""
    with open(os.path.join(ROOT, "docs", "ENV_VARS.md")) as f:
        live = f.read().split(RETIRED)[0]
    rows = [line for line in live.splitlines() if line.startswith("| `")]
    mentioned = {n for row in rows for n in NAME.findall(row)}
    headed = {n for row in rows for n in NAME.findall(row.split("|")[1])}
    return mentioned, headed


def test_every_option_the_package_names_is_documented():
    mentioned, _ = _doc_rows()
    # a name that ends in "_" is a family spelled with a wildcard
    # (``MXNET_TPU_*``): documented where a member of it is
    missing = {n for n in _package_names() - mentioned
               if not (n.endswith("_")
                       and any(m.startswith(n) for m in mentioned))}
    assert not missing, sorted(missing)


def test_every_documented_option_is_one_the_package_names():
    _, headed = _doc_rows()
    assert len(headed) > 50, headed  # the tables were found at all
    assert not headed - _package_names(), sorted(headed - _package_names())
