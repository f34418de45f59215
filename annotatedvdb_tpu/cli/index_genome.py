"""CLI: build the 2-bit packed reference genome index from a FASTA.

The framework's SeqRepo-equivalent setup step (the reference instead points
``--seqrepoProxyPath`` at a pre-built SeqRepo directory,
``Load/bin/load_vcf_file.py:247-286``).  The resulting ``.npz`` feeds
``--refGenome`` on the load CLIs: device-side ref-allele validation plus
canonical GA4GH sequence digests for VRS primary keys.

Usage:
    python -m annotatedvdb_tpu.cli.index_genome \\
        --fasta GRCh38.fa.gz --output ./grch38.npz [--digests]
"""

from __future__ import annotations

import argparse

from annotatedvdb_tpu.genome import ReferenceGenome
from annotatedvdb_tpu.types import chromosome_label


def main(argv=None) -> int:
    from annotatedvdb_tpu.utils.runtime import pin_platform

    # honor an explicit cpu pin, place the compile cache
    pin_platform("auto")

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fasta", required=True)
    ap.add_argument("--output", required=True, help="output .npz path")
    ap.add_argument("--digests", action="store_true",
                    help="precompute GA4GH sequence digests (slow; cached "
                         "into the index)")
    args = ap.parse_args(argv)

    genome = ReferenceGenome.from_fasta(args.fasta, log=print)
    if not genome.length:
        ap.error(f"no standard chromosomes found in {args.fasta}")
    if args.digests:
        for code in sorted(genome.length):
            d = genome.sequence_digest(code)
            print(f"chr{chromosome_label(code)}: SQ.{d}")
    genome.save(args.output)
    total = sum(genome.length.values())
    print(f"indexed {len(genome.length)} chromosomes, {total} bases "
          f"-> {args.output}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
