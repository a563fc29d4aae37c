"""Conformance audit: regenerate the native descriptor from live engine
traces, run the fail-closed checker over every descriptor, and print the
lowering matrix (the paper's central result, §8.1).

  PYTHONPATH=src python examples/conformance_audit.py
"""
from repro.core.checker import generate_matrix, write_outputs
from repro.core.native_descriptor import generate_native_descriptor
from repro.launch.compile_cache import enable_compile_cache


def main():
    path = generate_native_descriptor()
    print(f"regenerated native descriptor from live conformance traces: {path}\n")
    rows = generate_matrix()
    width = max(len(r.backend) for r in rows)
    for r in rows:
        missing = f"  missing: {', '.join(r.missing)}" if r.missing else ""
        print(f"{r.backend:<{width}}  {r.mode:<14} {r.adapter_depth:<18} -> {r.label}{missing}")
    stats = write_outputs()
    print(
        f"\n{stats['rows']} rows; native_sound={stats['native_sound']} "
        f"(this runtime), sound_with_adapter={stats['sound_with_adapter']}"
    )
    print("artifacts: results/lowering-matrix.{md,json}, results/descriptor-provenance.md")


if __name__ == "__main__":
    enable_compile_cache()
    main()
