"""Harness clock at submit -> the job script's own first timestamp."""


def read(run):
    st = run["stages"]
    a, b = st.at("job_submitted"), st.at("script_main")
    return None if a is None or b is None else b - a
