"""Provenance helpers shared by ``bench.py`` and the other benchmarks:
every result line stamps the revision it was measured on (``None`` where
the tree is not a git checkout), and a parent that relays a child's
one-line result parses it tolerantly.
"""

from __future__ import annotations

import json
import subprocess
from typing import Optional, Tuple


def git_head_sha(path: str) -> Optional[str]:
    """Short HEAD sha of the git repo containing ``path``, best-effort
    (None outside a repo, without git, or on any subprocess failure)."""
    try:
        out = subprocess.run(
            ["git", "-C", path, "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10)
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else None
    except (OSError, subprocess.SubprocessError):
        return None


def last_json_line(text: Optional[str],
                   want: type = dict) -> Tuple[Optional[str], object]:
    """Scan child stdout bottom-up for the last line parsing as JSON of
    type ``want``; returns ``(raw_line, parsed)`` or ``(None, None)``.

    The shared tolerant parse for every supervisor that relays a child's
    one-line result: library banners or interpreter-shutdown warnings
    printed after the ``json.dumps`` — and lines truncated mid-write by a
    SIGKILL — must fall through to the caller's retry path, not surface as
    corrupt JSON."""
    for line in reversed((text or "").strip().splitlines()):
        try:
            parsed = json.loads(line)
        except ValueError:
            continue
        if isinstance(parsed, want):
            return line, parsed
    return None, None
