"""Environment/compatibility report (reference ``deepspeed/env_report.py`` +
``bin/ds_report``): versions, accelerator status, and the op-builder
compatibility matrix, so users can see at a glance what this install can do.

The accelerator probe runs in a subprocess under a timeout: a hung TPU
plugin must degrade the report, not hang it (the reference equivalent is
``real_accelerator`` probing with try/except, ``real_accelerator.py:90``).
"""

import json
import os
import shutil
import subprocess
import sys

GREEN_OK = "\033[92m[OKAY]\033[0m"
RED_NO = "\033[91m[NO]\033[0m"


def _versions() -> dict:
    out = {"python": sys.version.split()[0]}
    for mod in ("jax", "jaxlib", "flax", "optax", "orbax.checkpoint", "numpy"):
        try:
            m = __import__(mod)
            for part in mod.split(".")[1:]:
                m = getattr(m, part)
            out[mod] = getattr(m, "__version__", "?")
        except Exception:
            out[mod] = "not installed"
    try:
        from deepspeed_tpu.version import __version__ as v
        out["deepspeed_tpu"] = v
    except Exception:
        out["deepspeed_tpu"] = "?"
    return out


def _probe_accelerator(timeout: int = 45) -> dict:
    code = ("import jax,json;"
            "print(json.dumps({'backend': jax.default_backend(),"
            "'devices': [str(d) for d in jax.devices()]}))")
    try:
        p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=timeout, env=dict(os.environ))
        for line in reversed(p.stdout.strip().splitlines()):
            if line.startswith("{"):
                return json.loads(line)
        return {"error": (p.stderr.strip().splitlines() or ["no output"])[-1]}
    except subprocess.TimeoutExpired:
        return {"error": f"accelerator probe timed out after {timeout}s "
                         "(TPU plugin unreachable?)"}
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def _op_compat() -> list:
    """(name, compatible, detail) per registered op builder (reference
    ds_report's op compatibility matrix over ALL_OPS)."""
    rows = []
    try:
        from deepspeed_tpu.ops.op_builder import ALL_BUILDERS
        for name, builder_cls in sorted(ALL_BUILDERS.items()):
            try:
                b = builder_cls()
                compat = b.is_compatible()
                ok, why = compat if isinstance(compat, tuple) else (bool(compat), "")
                if ok and not why:
                    why = f"compiler={b.compiler()}"
                rows.append((name, ok, why))
            except Exception as e:
                rows.append((name, False, f"{type(e).__name__}: {e}"))
    except Exception as e:
        rows.append(("op_builder registry", False, str(e)))
    return rows


def _toolchain() -> list:
    return [(tool, shutil.which(tool) or "not found")
            for tool in ("g++", "cmake", "ninja", "make")]


def main(argv=None) -> int:
    print("-" * 74)
    print("DeepSpeed-TPU environment report (ds_report)")
    print("-" * 74)
    print("\nversions:")
    for k, v in _versions().items():
        print(f"  {k:<18} {v}")
    print("\naccelerator:")
    acc = _probe_accelerator()
    if "error" in acc:
        print(f"  {RED_NO} {acc['error']}")
    else:
        print(f"  {GREEN_OK} backend={acc['backend']} devices={len(acc['devices'])}")
        for d in acc["devices"][:8]:
            print(f"         {d}")
    print("\nnative toolchain:")
    for tool, path in _toolchain():
        mark = GREEN_OK if path != "not found" else RED_NO
        print(f"  {mark} {tool:<8} {path}")
    print("\nop builder compatibility:")
    for name, ok, why in _op_compat():
        print(f"  {GREEN_OK if ok else RED_NO} {name:<22} {why}")
    print("-" * 74)
    return 0


if __name__ == "__main__":
    sys.exit(main())
