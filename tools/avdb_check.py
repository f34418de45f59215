#!/usr/bin/env python
"""Project-native static analysis driver (``annotatedvdb_tpu.analysis``).

Runs the nine AVDB rule families (trace-safety, lock-discipline,
registry-drift, env-var drift, CLI-contract, hygiene, async-safety,
device/host twin contract, durability protocol) over the tree.  See
README "Static analysis & code health" for the rule catalog and the
suppression policy (``# avdb: noqa[CODE] -- reason``).

Usage:
    python tools/avdb_check.py [--json] [--diff REV] [paths...]

Default paths: ``annotatedvdb_tpu tools tests bench.py chip_smoke.py``
relative to the repo root.  ``--diff REV`` analyzes only the ``.py`` files changed since
``REV`` (tracked changes + untracked files, fixture data excluded) — the
fast pre-commit mode; project-audit codes that need the full tree gate
themselves off automatically, and the tier-1 gate stays the full-tree
default.  Exit codes mirror ``tools/store_fsck.py``: 0 = clean,
1 = findings, 2 = usage/internal error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_PATHS = ("annotatedvdb_tpu", "tools", "tests", "bench.py",
                 "chip_smoke.py")


def _changed_files(root: str, rev: str) -> list:
    """Repo-absolute ``.py`` paths changed since ``rev``: the tracked diff
    plus untracked files, restricted to the tier-1 gate's scan scope
    (``DEFAULT_PATHS``) so the fast mode approximates — never exceeds —
    the full gate, minus deletions and the checked-in violation fixtures
    under ``tests/data`` (explicit file args bypass the walk's fixture
    exemption, so --diff must re-apply it)."""
    import subprocess

    rels: list = []
    for cmd in (
        ["git", "diff", "--name-only", rev],
        ["git", "ls-files", "--others", "--exclude-standard"],
    ):
        p = subprocess.run(cmd, capture_output=True, text=True, cwd=root)
        if p.returncode != 0:
            raise RuntimeError(
                f"`{' '.join(cmd)}` failed: {p.stderr.strip() or 'rc=' + str(p.returncode)}"
            )
        rels.extend(line.strip() for line in p.stdout.splitlines())
    out: list = []
    seen: set = set()
    for rel in rels:
        if not rel.endswith(".py") or rel in seen:
            continue
        seen.add(rel)
        norm = rel.replace("\\", "/")
        if norm.startswith("tests/data/"):
            continue  # violation fixtures are violations ON PURPOSE
        if not any(
            norm == d or norm.startswith(d + "/") for d in DEFAULT_PATHS
        ):
            continue  # outside the gate's scan scope: the full run never
            # judges it, so the pre-commit mode must not either
        full = os.path.join(root, rel)
        if os.path.isfile(full):  # a deleted file has nothing to analyze
            out.append(full)
    return sorted(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("paths", nargs="*",
                    help="files/directories to analyze "
                         f"(default: {' '.join(DEFAULT_PATHS)})")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable report on stdout")
    ap.add_argument("--diff", metavar="REV", default=None,
                    help="analyze only .py files changed since REV "
                         "(tracked diff + untracked; the fast pre-commit "
                         "mode — tier-1 keeps the full-tree default)")
    ap.add_argument("--loaderCli", action="append", default=None,
                    metavar="PATH",
                    help="override the CLI-contract file list (repeatable; "
                         "fixture tests point this at synthetic CLIs)")
    args = ap.parse_args(argv)

    from annotatedvdb_tpu.analysis import run_paths
    from annotatedvdb_tpu.analysis.core import find_repo_root

    root = find_repo_root(os.path.dirname(os.path.abspath(__file__)))
    if args.diff is not None:
        if args.paths:
            print("avdb_check: --diff and explicit paths are exclusive",
                  file=sys.stderr)
            return 2
        try:
            paths = _changed_files(root, args.diff)
        except RuntimeError as err:
            print(f"avdb_check: {err}", file=sys.stderr)
            return 2
        if not paths:
            if args.json:
                print(json.dumps({
                    "version": 1, "files_scanned": 0, "findings": [],
                    "exit_code": 0,
                }, indent=1, sort_keys=True))
            else:
                print(
                    f"avdb_check: no python files changed since "
                    f"{args.diff}", file=sys.stderr,
                )
            return 0
    else:
        paths = args.paths or [
            os.path.join(root, p) for p in DEFAULT_PATHS
            if os.path.exists(os.path.join(root, p))
        ]
        missing = [p for p in paths if not os.path.exists(p)]
        if missing:
            print(f"avdb_check: no such path: {', '.join(missing)}",
                  file=sys.stderr)
            return 2
    try:
        findings, n_files = run_paths(
            paths,
            loader_clis=(
                tuple(args.loaderCli) if args.loaderCli else None
            ),
            audit=args.diff is None,
        )
    except Exception as err:  # internal analyzer error, not a finding
        print(f"avdb_check: internal error: {err!r}", file=sys.stderr)
        return 2
    exit_code = 1 if findings else 0
    if args.json:
        print(json.dumps({
            "version": 1,
            "files_scanned": n_files,
            "findings": [f.as_dict() for f in findings],
            "exit_code": exit_code,
        }, indent=1, sort_keys=True))
    else:
        for f in findings:
            print(f.render())
        print(
            f"avdb_check: {n_files} file(s), {len(findings)} finding(s)",
            file=sys.stderr,
        )
    return exit_code


if __name__ == "__main__":
    raise SystemExit(main())
