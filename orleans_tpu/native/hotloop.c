/* hotloop — native per-callback runner for the host-loop occupancy
 * profiler (observability.profiling.LoopProfiler).
 *
 * The profiler interposes on the event loop's call_soon/call_at and
 * times EVERY callback the loop runs.  In pure Python that prologue/
 * epilogue costs ~1.3us per callback (a Python frame, two
 * time.perf_counter calls, a contextvar read, two dict upserts, ~10
 * slot accesses) — measurable against the ~2.5us a trivial loop
 * callback costs at all.  This module is the same accounting as
 * LoopProfiler._run_cb/set_category compiled to C (~0.2-0.3us): the
 * loop schedules ONE Runner instance with the real callback as its
 * first argument, the Runner vectorcalls the callback between two
 * clock reads, and attributes the elapsed time into the shared
 * window-category dict.
 *
 * Division of labour: the Runner owns the HOT state (mark / last_end /
 * win_start / top_min / next_slow / stop_s / depth / closed / adopted
 * scalars, the current category + label, the open window's
 * category->seconds dict) and exposes every field as a writable member,
 * so the Python LoopProfiler's slow paths (the fold into the stats
 * registry, window finalize, flight-recorder trigger, flush, enter/exit
 * token discipline) keep operating on the very same state through
 * delegating properties.  The two rare epilogue branches — a slow
 * callback (top-K admission, or a stop: longer than stop_s) and the
 * periodic slow path (next_slow) — call back into the Python profiler.
 *
 * A callback that switches category (set_category) keeps its segments'
 * seconds by category (up to NSEG), so a slow callback is recorded under
 * the category that held most of it: the owner of a stop.
 *
 * ``adopted``: the callback that installed the account was already
 * running when the hooks went in, so no runner opened it; install marks
 * it open (depth 1, adopted 1) and the next runner entry — which the
 * loop only makes once that callback has returned — closes it there, so
 * its time is busy and not idle.
 *
 * Clock: CLOCK_MONOTONIC, the same base CPython uses for
 * time.perf_counter on Linux, so C-side stamps and Python-side stamps
 * interchange freely.
 *
 * Error discipline: accounting failures (OOM on a dict upsert) are
 * reported via PyErr_WriteUnraisable and never mask or corrupt the
 * wrapped callback's own result/exception; the callback's exception is
 * held across the epilogue's Python calls and re-raised unchanged.
 */

#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <structmember.h>
#include <time.h>

static inline double mono_clock(void) {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return (double)ts.tv_sec + (double)ts.tv_nsec * 1e-9;
}

/* interned keys / method names, set at module init */
static PyObject *s_idle, *s_other, *s_record_top, *s_slow;

#define NSEG 8

typedef struct {
    PyObject_HEAD
    vectorcallfunc vcall;
    double mark;        /* last attribution boundary */
    double last_end;    /* end of the previous callback (idle from) */
    double win_start;   /* current window start */
    double top_min;     /* top-K admission bar */
    double window;      /* seconds per occupancy slice */
    double next_slow;   /* the periodic slow path is due at this time */
    double stop_s;      /* a callback longer than this is a stop */
    double start;       /* start of the open callback */
    int depth;          /* >0 while inside a wrapped callback */
    int closed;         /* uninstalled: pass callbacks straight through */
    int adopted;        /* the open callback is the one that installed */
    int nseg;           /* categories the open callback switched through */
    PyObject *seg_cat[NSEG]; /* their categories (owned) */
    double seg_s[NSEG];      /* and seconds */
    PyObject *cur;      /* category accruing since mark (str) */
    PyObject *cb_label; /* label for the top-K record, or None */
    PyObject *win_cats; /* dict: category -> seconds (open window) */
    PyObject *cat_var;  /* the LOOP_CATEGORY contextvar */
    PyObject *profiler; /* the owning LoopProfiler (slow paths) */
} Runner;

/* win_cats[key] += v (missing -> v).  Failures never propagate: the
 * callback's own outcome must not be masked by accounting. */
static void dict_add(PyObject *d, PyObject *key, double v) {
    if (d == NULL || key == NULL)
        return;
    PyObject *old = PyDict_GetItemWithError(d, key); /* borrowed */
    if (old == NULL && PyErr_Occurred())
        goto fail;
    if (old != NULL) {
        double prev = PyFloat_AsDouble(old);
        if (prev == -1.0 && PyErr_Occurred())
            goto fail;
        v += prev;
    }
    PyObject *f = PyFloat_FromDouble(v);
    if (f == NULL)
        goto fail;
    int rc = PyDict_SetItem(d, key, f);
    Py_DECREF(f);
    if (rc < 0)
        goto fail;
    return;
fail:
    PyErr_WriteUnraisable(d);
}

/* call profiler.<name>(...) with any pending exception preserved
 * (a2..a4 may be NULL — ObjArgs terminates at the first NULL) */
static void call_slow_path(Runner *r, PyObject *name, PyObject *a1,
                           PyObject *a2, PyObject *a3, PyObject *a4) {
    PyObject *exc_type, *exc_val, *exc_tb;
    PyErr_Fetch(&exc_type, &exc_val, &exc_tb);
    PyObject *res = PyObject_CallMethodObjArgs(r->profiler, name, a1, a2,
                                               a3, a4, NULL);
    if (res == NULL)
        PyErr_WriteUnraisable(r->profiler);
    else
        Py_DECREF(res);
    PyErr_Restore(exc_type, exc_val, exc_tb);
}

/* book d seconds of the open callback to its current category, in the
 * window and among the callback's own segments */
static void seg_add(Runner *r, double d) {
    dict_add(r->win_cats, r->cur, d);
    for (int i = 0; i < r->nseg; i++) {
        if (r->seg_cat[i] == r->cur) {
            r->seg_s[i] += d;
            return;
        }
    }
    if (r->nseg < NSEG) {
        r->seg_cat[r->nseg] = Py_NewRef(r->cur);
        r->seg_s[r->nseg++] = d;
    }
}

static void seg_clear(Runner *r) {
    for (int i = 0; i < r->nseg; i++)
        Py_CLEAR(r->seg_cat[i]);
    r->nseg = 0;
}

/* the epilogue of one callback (``cb`` is what the top-K record
 * describes): accrue its last segment, record it if it was slow, run the
 * periodic slow path when due.  The caller holds any pending exception. */
static void close_callback(Runner *r, PyObject *cb, double end) {
    r->depth = 0;
    double d = end - r->mark;
    double dur = end - r->start;
    if (r->nseg && (dur > r->top_min || dur > r->stop_s)) {
        if (d > 0.0)
            seg_add(r, d);
    } else if (d > 0.0) {
        dict_add(r->win_cats, r->cur, d);
    }
    r->last_end = end;
    if (dur > r->top_min || dur > r->stop_s) {
        /* a slow callback (rare: the top-K bar rises to the K-th
         * slowest as the window fills; a stop is longer than the switch
         * interval).  Recorded under the category that held most of
         * it, at its start offset WITHIN the open window, so the
         * Perfetto flame row places it exactly. */
        PyObject *owner = r->cur;
        double most = -1.0;
        for (int i = 0; i < r->nseg; i++) {
            if (r->seg_s[i] > most) {
                most = r->seg_s[i];
                owner = r->seg_cat[i];
            }
        }
        Py_INCREF(owner);
        PyObject *durf = PyFloat_FromDouble(dur);
        PyObject *off = PyFloat_FromDouble(r->start - r->win_start);
        if (durf != NULL && off != NULL)
            call_slow_path(r, s_record_top, cb, durf, off, owner);
        Py_XDECREF(durf);
        Py_XDECREF(off);
        Py_DECREF(owner);
    }
    seg_clear(r);
    if (end >= r->next_slow) {
        /* the fold into the stats registry and the window's finalize */
        PyObject *endf = PyFloat_FromDouble(end);
        if (endf != NULL) {
            call_slow_path(r, s_slow, endf, NULL, NULL, NULL);
            Py_DECREF(endf);
        }
    }
}

static PyObject *runner_vectorcall(PyObject *self, PyObject *const *args,
                                   size_t nargsf, PyObject *kwnames) {
    Runner *r = (Runner *)self;
    Py_ssize_t nargs = PyVectorcall_NARGS(nargsf);
    if (nargs < 1 || (kwnames != NULL && PyTuple_GET_SIZE(kwnames) > 0)) {
        PyErr_SetString(PyExc_TypeError,
                        "Runner(callback, *args) takes a positional "
                        "callback and its positional arguments");
        return NULL;
    }
    PyObject *cb = args[0];
    if (r->closed)
        return PyObject_Vectorcall(cb, args + 1, nargs - 1, NULL);
    if (r->depth && !r->adopted) {
        /* nested invocation (a wrapped fn called synchronously from
         * inside another): inner boundaries are a no-op */
        r->depth++;
        PyObject *res = PyObject_Vectorcall(cb, args + 1, nargs - 1, NULL);
        r->depth--;
        return res;
    }
    double now = mono_clock();
    if (r->depth) {
        /* the loop runs a callback only after the one before returned:
         * the callback that installed the account ended by now */
        r->adopted = 0;
        close_callback(r, Py_None, now);
    }
    double gap = now - r->last_end;
    if (gap > 0.0)
        /* the loop was in select() between callbacks: idle */
        dict_add(r->win_cats, s_idle, gap);
    r->depth = 1;
    r->mark = r->start = now;
    PyObject *cur;
    if (PyContextVar_Get(r->cat_var, s_other, &cur) < 0) {
        PyErr_WriteUnraisable(self);
        cur = Py_NewRef(s_other);
    }
    Py_XSETREF(r->cur, cur);                 /* owned */
    Py_XSETREF(r->cb_label, Py_NewRef(Py_None));

    PyObject *res = PyObject_Vectorcall(cb, args + 1, nargs - 1, NULL);

    /* hold the callback's exception across the whole epilogue: dict
     * lookups misread a pending exception as their own failure (and
     * would swallow it via PyErr_WriteUnraisable) */
    PyObject *exc_type = NULL, *exc_val = NULL, *exc_tb = NULL;
    if (res == NULL)
        PyErr_Fetch(&exc_type, &exc_val, &exc_tb);
    close_callback(r, cb, mono_clock());
    if (res == NULL)
        PyErr_Restore(exc_type, exc_val, exc_tb);
    return res; /* NULL propagates the callback's exception unchanged */
}

/* set_category(category, label=None): accrue to the current category up
 * to now, then switch — a stage span opening or closing inside the
 * callback, or a turn labelling its task. */
static PyObject *runner_set_category(PyObject *self, PyObject *const *args,
                                     Py_ssize_t nargs) {
    Runner *r = (Runner *)self;
    if (nargs < 1 || nargs > 2) {
        PyErr_SetString(PyExc_TypeError,
                        "set_category(category, label=None)");
        return NULL;
    }
    if (!r->depth || r->closed)
        Py_RETURN_NONE; /* outside a wrapped callback: no loop time */
    double now = mono_clock();
    double d = now - r->mark;
    if (d > 0.0)
        seg_add(r, d);
    r->mark = now;
    Py_XSETREF(r->cur, Py_NewRef(args[0]));
    if (nargs == 2 && args[1] != Py_None)
        Py_XSETREF(r->cb_label, Py_NewRef(args[1]));
    Py_RETURN_NONE;
}

/* adopt(): the calling callback — the one installing the account — is
 * open from now on; the next runner entry closes it */
static PyObject *runner_adopt(PyObject *self, PyObject *unused) {
    Runner *r = (Runner *)self;
    if (r->depth || r->closed)
        Py_RETURN_NONE;
    PyObject *cur;
    if (PyContextVar_Get(r->cat_var, s_other, &cur) < 0)
        return NULL;
    Py_XSETREF(r->cur, cur);
    Py_XSETREF(r->cb_label, Py_NewRef(Py_None));
    r->mark = r->start = mono_clock();
    r->depth = 1;
    r->adopted = 1;
    Py_RETURN_NONE;
}

static int runner_init(PyObject *self, PyObject *args, PyObject *kw) {
    Runner *r = (Runner *)self;
    PyObject *cat_var, *profiler;
    if (!PyArg_ParseTuple(args, "OO", &cat_var, &profiler))
        return -1;
    r->vcall = runner_vectorcall;
    double now = mono_clock();
    r->mark = r->last_end = r->win_start = now;
    r->top_min = 0.0;
    r->window = 1.0;
    r->next_slow = now + 1.0;
    r->stop_s = 0.005;
    r->start = now;
    r->depth = 0;
    r->closed = 0;
    r->adopted = 0;
    seg_clear(r);
    Py_XSETREF(r->cur, Py_NewRef(s_other));
    Py_XSETREF(r->cb_label, Py_NewRef(Py_None));
    PyObject *d = PyDict_New();
    if (d == NULL)
        return -1;
    Py_XSETREF(r->win_cats, d);
    Py_XSETREF(r->cat_var, Py_NewRef(cat_var));
    Py_XSETREF(r->profiler, Py_NewRef(profiler));
    return 0;
}

static int runner_traverse(PyObject *self, visitproc visit, void *arg) {
    Runner *r = (Runner *)self;
    Py_VISIT(r->cur);
    Py_VISIT(r->cb_label);
    Py_VISIT(r->win_cats);
    Py_VISIT(r->cat_var);
    Py_VISIT(r->profiler);
    for (int i = 0; i < r->nseg; i++)
        Py_VISIT(r->seg_cat[i]);
    return 0;
}

static int runner_clear(PyObject *self) {
    Runner *r = (Runner *)self;
    Py_CLEAR(r->cur);
    Py_CLEAR(r->cb_label);
    Py_CLEAR(r->win_cats);
    Py_CLEAR(r->cat_var);
    Py_CLEAR(r->profiler);
    seg_clear(r);
    return 0;
}

static void runner_dealloc(PyObject *self) {
    PyObject_GC_UnTrack(self);
    runner_clear(self);
    Py_TYPE(self)->tp_free(self);
}

static PyMemberDef runner_members[] = {
    {"mark", T_DOUBLE, offsetof(Runner, mark), 0,
     "last attribution boundary (perf_counter base)"},
    {"last_end", T_DOUBLE, offsetof(Runner, last_end), 0,
     "end of the previous callback"},
    {"win_start", T_DOUBLE, offsetof(Runner, win_start), 0,
     "current window start"},
    {"top_min", T_DOUBLE, offsetof(Runner, top_min), 0,
     "top-K admission bar"},
    {"window", T_DOUBLE, offsetof(Runner, window), 0,
     "seconds per occupancy slice"},
    {"next_slow", T_DOUBLE, offsetof(Runner, next_slow), 0,
     "the periodic slow path is due at this time"},
    {"stop_s", T_DOUBLE, offsetof(Runner, stop_s), 0,
     "a callback longer than this is a stop"},
    {"start", T_DOUBLE, offsetof(Runner, start), 0,
     "start of the open callback (tells one callback from the next)"},
    {"adopted", T_INT, offsetof(Runner, adopted), 0,
     "the open callback is the one that installed the account"},
    {"depth", T_INT, offsetof(Runner, depth), 0,
     ">0 while inside a wrapped callback"},
    {"closed", T_INT, offsetof(Runner, closed), 0,
     "uninstalled: callbacks pass straight through"},
    {"cur", T_OBJECT, offsetof(Runner, cur), 0,
     "category accruing since mark"},
    {"cb_label", T_OBJECT, offsetof(Runner, cb_label), 0,
     "top-K label for the current callback, or None"},
    {"win_cats", T_OBJECT, offsetof(Runner, win_cats), 0,
     "open window's category -> seconds dict"},
    {"cat_var", T_OBJECT, offsetof(Runner, cat_var), READONLY,
     "the LOOP_CATEGORY contextvar"},
    {NULL},
};

static PyMethodDef runner_methods[] = {
    {"set_category", (PyCFunction)(void (*)(void))runner_set_category,
     METH_FASTCALL,
     "set_category(category, label=None): accrue and switch the "
     "attribution category within the current callback."},
    {"adopt", (PyCFunction)runner_adopt, METH_NOARGS,
     "adopt(): book the calling (unwrapped) callback from now on; the "
     "next runner entry closes it."},
    {NULL, NULL, 0, NULL},
};

static PyTypeObject RunnerType = {
    PyVarObject_HEAD_INIT(NULL, 0)
    .tp_name = "_hotloop.Runner",
    .tp_basicsize = sizeof(Runner),
    .tp_flags = Py_TPFLAGS_DEFAULT | Py_TPFLAGS_HAVE_GC |
                Py_TPFLAGS_HAVE_VECTORCALL,
    .tp_doc = "Native per-callback occupancy runner (see module doc).",
    .tp_new = PyType_GenericNew,
    .tp_init = runner_init,
    .tp_dealloc = runner_dealloc,
    .tp_traverse = runner_traverse,
    .tp_clear = runner_clear,
    .tp_call = PyVectorcall_Call,
    .tp_vectorcall_offset = offsetof(Runner, vcall),
    .tp_members = runner_members,
    .tp_methods = runner_methods,
};

static struct PyModuleDef hl_module = {
    PyModuleDef_HEAD_INIT, "_hotloop",
    "Native host-loop occupancy runner for orleans_tpu.", -1, NULL,
};

PyMODINIT_FUNC PyInit__hotloop(void) {
    s_idle = PyUnicode_InternFromString("idle");
    s_other = PyUnicode_InternFromString("other");
    s_record_top = PyUnicode_InternFromString("_record_top");
    s_slow = PyUnicode_InternFromString("_slow");
    if (!s_idle || !s_other || !s_record_top || !s_slow)
        return NULL;
    if (PyType_Ready(&RunnerType) < 0)
        return NULL;
    PyObject *m = PyModule_Create(&hl_module);
    if (m == NULL)
        return NULL;
    Py_INCREF(&RunnerType);
    if (PyModule_AddObject(m, "Runner", (PyObject *)&RunnerType) < 0) {
        Py_DECREF(&RunnerType);
        Py_DECREF(m);
        return NULL;
    }
    return m;
}
