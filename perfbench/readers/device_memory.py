"""Peak bytes in use on the fullest chip, as the backend reports it
after the window."""


def read(spec, view):
    return view["deployment"].memory_peak_bytes()
