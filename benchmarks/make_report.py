"""Benchmark report generation: inject the generated roofline tables
into ``EXPERIMENTS.md`` placeholders.

  PYTHONPATH=src python -m benchmarks.make_report [roofline] [path]
"""
import re
import sys

MARKERS = {
    "<!-- ROOFLINE_BASELINE_SP -->": ("pod16x16", ""),
    "<!-- ROOFLINE_OPT_SP -->": ("pod16x16", "__opt"),
}


def roofline(path="EXPERIMENTS.md"):
    from benchmarks.roofline_report import table
    src = open(path).read()
    for marker, (mesh, suffix) in MARKERS.items():
        t = table(mesh, suffix)
        block = f"{marker}\n{t}\n<!-- /generated -->"
        # replace marker (+ any previously generated block)
        pat = re.escape(marker) + r"(?:\n.*?<!-- /generated -->)?"
        src = re.sub(pat, block, src, flags=re.S)
    open(path, "w").write(src)
    print("EXPERIMENTS.md tables refreshed")


def main(argv):
    if argv and argv[0] == "roofline":
        argv = argv[1:]
    roofline(*argv[:1])


if __name__ == "__main__":
    main(sys.argv[1:])
