"""`edl` console entry point: train | evaluate | predict | clean.

Parity: reference elasticdl/python/elasticdl/client.py:13-46. The
subcommand implementations live in elasticdl_tpu.api: cluster submission
(image build + master pod) when ``--docker_image_repository`` is set,
else the local mode (master + workers as processes on this TPU VM). This
shim stays import-light so failures surface as a clear message, not a
ModuleNotFoundError.
"""

import sys


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    try:
        from elasticdl_tpu import api
    except ImportError:
        print(
            "elasticdl_tpu client API is not available in this build",
            file=sys.stderr,
        )
        return 2
    return api.cli_main(argv)


if __name__ == "__main__":
    sys.exit(main())
