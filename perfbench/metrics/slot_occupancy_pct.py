"""Active slots over slots, from ServingEngine.stats() as the job script
sampled it through the window."""


def read(run):
    st, job = run["stages"], run["job"]
    t0, t1 = st.at("window_start"), st.at("window_end")
    inside = [row[1] for row in job["occupancy"] if t0 <= row[0] < t1]
    if not inside:
        return None
    return 100.0 * sum(inside) / len(inside) / job["slots"]
