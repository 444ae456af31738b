"""Process start to window open, on the host's clock."""


def read(spec, view):
    return view["setup_s"]
