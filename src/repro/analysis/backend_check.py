"""RL301–RL303: kernel backend registry checker.

`kernels/backend.py` promises that every op dispatches identically
through the ``reference`` and ``pallas`` backends — model call sites
pass the declared :data:`repro.kernels.backend.OP_SURFACE` arguments
and expect either implementation to accept them. Registration already
enforces signatures at import time (``BackendContractError``); this
checker re-runs the same contract under lint so CI reports *which* op
drifted even when an import-time failure is being bisected, and adds
registry-completeness checks imports alone cannot see:

  * RL301 — a registered implementation whose Python signature cannot
    serve the declared op surface (checked via
    ``backend.check_op_signature``);
  * RL302 — a kernel module in ``repro/kernels/`` that backend.py
    never imports: the kernel exists but no backend can reach it;
  * RL303 — a required backend name missing from the registry, or a
    registered backend missing an op implementation.

The signature checks import the live registry (the analyzer runs in
the repo's own environment); RL302 is static over backend.py's import
statements so it works on any checkout.
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import List

from repro.analysis.findings import Finding, make_finding

#: backends every checkout must register
REQUIRED_BACKENDS = ("reference", "pallas")

#: kernels/ modules that are not kernel implementations
_NON_KERNEL_MODULES = {"__init__", "ref", "backend", "tiling"}


def analyze_backend_registry(kernels_dir: Path) -> List[Finding]:
    findings: List[Finding] = []
    backend_py = kernels_dir / "backend.py"
    rel = backend_py

    # ---- RL302: every kernel module is imported by backend.py --------
    imported: set = set()
    line_of_imports = 1
    if backend_py.exists():
        tree = ast.parse(backend_py.read_text())
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                parts = node.module.split(".")
                if "kernels" in parts:
                    imported.add(parts[-1])
                    line_of_imports = node.lineno
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    parts = alias.name.split(".")
                    if "kernels" in parts:
                        imported.add(parts[-1])
    for mod in sorted(p.stem for p in kernels_dir.glob("*.py")):
        if mod in _NON_KERNEL_MODULES:
            continue
        if mod not in imported:
            findings.append(make_finding(
                "RL302", rel, line_of_imports,
                f"kernel module {mod!r} is not imported by the backend "
                f"registry",
                "wire it into a KernelBackend (or fold it into ref.py "
                "if it is an oracle)"))

    # ---- RL301/RL303: live registry introspection --------------------
    try:
        from repro.kernels import backend as KB
    except Exception as e:          # import raises on contract errors
        findings.append(make_finding(
            "RL303", rel, 1,
            f"kernel backend registry failed to import: {e}",
            "fix the registration error; see BackendContractError"))
        return findings

    registered = KB.available_backends()
    for name in REQUIRED_BACKENDS:
        if name not in registered:
            findings.append(make_finding(
                "RL303", rel, 1,
                f"required backend {name!r} is not registered "
                f"(have {registered})",
                "register_backend(KernelBackend(name=...))"))
    for name in registered:
        be = KB.get_backend(name)
        for op, defect in sorted(KB.validate_backend(be).items()):
            rule = "RL303" if "not implemented" in defect else "RL301"
            findings.append(make_finding(
                rule, rel, 1,
                f"backend {name!r} op {op!r}: {defect}",
                f"align the implementation with OP_SURFACE[{op!r}]"))
    return findings
