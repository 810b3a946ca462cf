"""Process start to window start: imports, codec, input generation, cache
reads or compiles, the warm-up step."""
UNIT = "s"


def read(cell):
    return cell.setup_s
