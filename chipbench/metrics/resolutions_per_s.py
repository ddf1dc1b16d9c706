"""Problems answered in requests that completed inside the window, per
second of window."""


def read(run):
    done = sum(n for _, t_done, n, ok in run.requests
               if ok and t_done is not None and t_done <= run.seconds)
    return done / run.seconds
