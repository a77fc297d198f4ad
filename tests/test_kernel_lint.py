"""Static lint over the Pallas kernel sources: the ``pallas-index`` rule.

The rule flags a bare Python int in a ``pl.load`` / ``pl.store`` /
``pl.swap`` index tuple, a form older JAX releases rejected in interpret
mode.  The installed jax 0.9.0 has none of these functions: kernels index
refs directly (``k_ref[0, pl.ds(s * bk, bk), :]``), and a kernel that
called them would fail as soon as it is traced.  The rule still runs on
every kernel file, naming the offender in the test id.
"""
import textwrap
from pathlib import Path

import pytest

from repro.analysis import PallasIndexRule, run_analysis

SRC = Path(__file__).parent.parent / "src"
KERNELS_DIR = SRC / "repro" / "kernels"


def _kernel_sources() -> list[Path]:
    return sorted(KERNELS_DIR.rglob("*.py"))


def test_kernel_sources_exist():
    assert _kernel_sources(), f"no kernel sources under {KERNELS_DIR}"


@pytest.mark.parametrize("path", _kernel_sources(),
                         ids=lambda p: str(p.relative_to(KERNELS_DIR)))
def test_no_bare_int_pl_load_indices(path):
    report = run_analysis(SRC, rules=[PallasIndexRule()], files=[path])
    assert not report.findings, "\n".join(
        f"{f.path}:{f.line}: {f.message}" for f, _ in report.findings
    )


def test_rule_catches_known_bad_pattern(tmp_path):
    """The exact shape of the PR 3 bug — plus the swap variant and a
    multi-line call the old regex needed whitespace-flattening for —
    must still be caught after the AST migration."""
    bad = tmp_path / "repro" / "kernels" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(textwrap.dedent("""\
        from jax.experimental import pallas as pl

        def kernel(q_ref, o_ref):
            row = pl.load(q_ref, (0, pl.ds(0, 4)))
            pl.store(
                o_ref,
                (pl.ds(0, 4),
                 0),
                row,
            )
            pl.swap(o_ref, (-1, pl.ds(0, 4)), row)
    """))
    report = run_analysis(tmp_path, rules=[PallasIndexRule()])
    lines = sorted(f.line for f, _ in report.findings)
    assert lines == [4, 5, 11], report.findings
