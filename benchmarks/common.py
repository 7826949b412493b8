"""Shared CLI surface for the bench family (ISSUE 9 / DESIGN.md §13).

Every bench — pipeline, hostmodel, chain, adversarial, streaming — used to
hand-roll its own ``argparse`` setup, and the shared flags drifted: some had
``--no-verify``, some could not skip their oracle at all, only one took
``--backend``.  ``base_parser()`` is the single parent parser defining the
five flags every bench accepts with identical spellings and defaults:

  ``--tiny``       CI-smoke geometry (each bench documents its tiny shape);
  ``--json PATH``  write the schema-v2 BENCH artifact (artifacts.py);
  ``--no-verify``  skip the bench's oracle cross-check;
  ``--oracle``     force the oracle cross-check on where a bench defaults
                   it off (mutually exclusive with ``--no-verify``);
  ``--backend``    dataplane backend(s) (repro.backend).  Benches that run
                   one backend reject a multi-value sweep via
                   ``single_backend``; bench_pipeline sweeps them.

Bench-specific flags stay in each bench, added on top of the parent.
"""
from __future__ import annotations

import argparse

BACKEND_CHOICES = ("ref", "pallas", "pallas_interpret", "auto")


def base_parser() -> argparse.ArgumentParser:
    """The parent parser (``add_help=False``) carrying the shared flags."""
    p = argparse.ArgumentParser(add_help=False)
    p.add_argument("--tiny", action="store_true",
                   help="CI smoke: the bench's documented tiny geometry")
    p.add_argument("--json", metavar="PATH",
                   help="also write the BENCH json artifact here "
                        "(benchmarks/artifacts.py schema v2)")
    p.add_argument("--no-verify", action="store_true",
                   help="skip this bench's oracle cross-check")
    p.add_argument("--oracle", action="store_true",
                   help="force the oracle cross-check on where this bench "
                        "defaults it off")
    p.add_argument("--backend", nargs="+", default=None,
                   choices=list(BACKEND_CHOICES),
                   help="dataplane backend(s) (repro.backend); benches "
                        "that run one backend reject a multi-value sweep")
    return p


def make_parser(description: str) -> argparse.ArgumentParser:
    """A bench's parser: the shared parent plus room for its own flags."""
    return argparse.ArgumentParser(description=description,
                                   parents=[base_parser()])


def check_flags(ap: argparse.ArgumentParser, args) -> None:
    """Shared post-parse validation and set-up; call right after
    ``parse_args``.  Also turns on the persistent compilation cache
    (``repro.compile_cache``)."""
    if args.no_verify and args.oracle:
        ap.error("--no-verify and --oracle are mutually exclusive")
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()


def single_backend(ap: argparse.ArgumentParser, args) -> str | None:
    """The one backend for a non-sweeping bench; None = bench default."""
    if args.backend is None:
        return None
    if len(args.backend) > 1:
        ap.error("this bench runs a single --backend "
                 "(bench_pipeline sweeps them)")
    return args.backend[0]


def print_rows(rows) -> None:
    """The common ``name,value,derived`` CSV emission."""
    print("name,value,derived")
    for row in rows:
        name, value, derived = row[0], row[1], row[2]
        print(f"{name},{value},{str(derived).replace(',', ';')}")
