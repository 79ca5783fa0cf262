/* C API implementation: embeds CPython and drives flexflow_tpu.
 *
 * Mirror-image of the reference architecture: the reference embeds a
 * Python interpreter inside a Legion task (python/main.cc) and wraps a
 * C++ core in C for cffi (python/flexflow_c.cc); here the core is Python,
 * so the C surface embeds the interpreter.  All handles are PyObject*.
 */

#include "flexflow_c.h"

#include <Python.h>

#include <cstdio>
#include <cstring>
#include <ctime>
#include <string>
#include <vector>

namespace {

PyObject* g_module = nullptr;   // flexflow_tpu
PyObject* g_np = nullptr;       // numpy

bool ensure_init() {
  if (g_module) return true;
  if (!Py_IsInitialized()) {
    Py_InitializeEx(0);
  }
  // FLEXFLOW_TPU_PLATFORM=cpu|tpu|... selects the JAX platform for an
  // embedding host program that cannot set JAX_PLATFORMS itself.
  const char* plat = getenv("FLEXFLOW_TPU_PLATFORM");
  if (plat && *plat) {
    std::string code = "import jax\njax.config.update('jax_platforms', '";
    code += plat;
    code += "')\n";
    PyRun_SimpleString(code.c_str());
  }
  g_module = PyImport_ImportModule("flexflow_tpu");
  if (!g_module) {
    PyErr_Print();
    return false;
  }
  g_np = PyImport_ImportModule("numpy");
  if (!g_np) {
    PyErr_Print();
    return false;
  }
  return true;
}

// Steals the reference to ``args`` (every call site builds a fresh tuple
// inline); kwargs stays borrowed.  A NULL ``args`` (failed Py_BuildValue,
// e.g. from a NULL handle) is reported, not dereferenced.
PyObject* call(PyObject* obj, const char* method, PyObject* args,
               PyObject* kwargs = nullptr) {
  if (!args) {
    PyErr_Print();
    PyErr_Clear();
    return nullptr;
  }
  PyObject* fn = PyObject_GetAttrString(obj, method);
  if (!fn) { PyErr_Print(); Py_DECREF(args); return nullptr; }
  PyObject* res = PyObject_Call(fn, args, kwargs);
  Py_DECREF(fn);
  Py_DECREF(args);
  if (!res) PyErr_Print();
  return res;
}

// Build a numpy array copying C data. fmt: 'f' float32, 'i' int32.
PyObject* np_array(const void* data, int64_t count, const int* dims, int ndims,
                   char fmt) {
  PyObject* list = PyList_New(count);
  if (fmt == 'f') {
    const float* p = static_cast<const float*>(data);
    for (int64_t i = 0; i < count; i++)
      PyList_SET_ITEM(list, i, PyFloat_FromDouble(p[i]));
  } else {
    const int32_t* p = static_cast<const int32_t*>(data);
    for (int64_t i = 0; i < count; i++)
      PyList_SET_ITEM(list, i, PyLong_FromLong(p[i]));
  }
  PyObject* arr = call(g_np, "array", Py_BuildValue("(O)", list),
                       Py_BuildValue("{s:s}", "dtype",
                                     fmt == 'f' ? "float32" : "int32"));
  Py_DECREF(list);
  if (!arr) return nullptr;
  if (ndims > 1) {
    PyObject* shape = PyTuple_New(ndims);
    for (int i = 0; i < ndims; i++)
      PyTuple_SET_ITEM(shape, i, PyLong_FromLong(dims[i]));
    PyObject* reshaped = call(arr, "reshape", Py_BuildValue("(O)", shape));
    Py_DECREF(shape);
    Py_DECREF(arr);
    return reshaped;
  }
  return arr;
}

PyObject* H(void* impl) { return static_cast<PyObject*>(impl); }

const char* kActNames[] = {"none", "relu", "sigmoid", "tanh"};

// Per-model pending batch: dict tensor-> array kept on the model object
// via a Python attribute so lifetimes follow the model handle.
int stage_input(flexflow_model_t m, PyObject* tensor, PyObject* arr) {
  if (!arr) return -1;
  PyObject* model = H(m.impl);
  PyObject* staged = PyObject_GetAttrString(model, "_c_api_batch");
  if (!staged || staged == Py_None) {
    Py_XDECREF(staged);
    staged = PyDict_New();
    PyObject_SetAttrString(model, "_c_api_batch", staged);
  }
  PyDict_SetItem(staged, tensor, arr);
  Py_DECREF(staged);
  Py_DECREF(arr);
  return 0;
}

int flush_batch_if_ready(flexflow_model_t m) {
  PyObject* model = H(m.impl);
  PyObject* staged = PyObject_GetAttrString(model, "_c_api_batch");
  PyObject* label = PyObject_GetAttrString(model, "_c_api_label");
  int ok = -1;
  if (staged && staged != Py_None && label && label != Py_None) {
    PyObject* res = call(model, "set_batch",
                         Py_BuildValue("(OO)", staged, label));
    if (res) { ok = 0; Py_DECREF(res); }
    PyObject_SetAttrString(model, "_c_api_batch", Py_None);
    PyObject_SetAttrString(model, "_c_api_label", Py_None);
  } else {
    ok = 0;  // nothing staged: batch already set
  }
  Py_XDECREF(staged);
  Py_XDECREF(label);
  return ok;
}

}  // namespace

extern "C" {

int flexflow_init(void) { return ensure_init() ? 0 : -1; }

void flexflow_finalize(void) { /* keep interpreter alive: cheap + safe */ }

flexflow_config_t flexflow_config_create(int batch_size, int epochs,
                                         int num_devices) {
  flexflow_config_t out{nullptr};
  if (!ensure_init()) return out;
  PyObject* cls = PyObject_GetAttrString(g_module, "FFConfig");
  PyObject* kw = Py_BuildValue("{s:i,s:i}", "batch_size", batch_size,
                               "epochs", epochs);
  if (num_devices > 0) {
    PyObject* v = PyLong_FromLong(num_devices);
    PyDict_SetItemString(kw, "workers_per_node", v);
    Py_DECREF(v);
  }
  PyObject* empty = PyTuple_New(0);
  out.impl = PyObject_Call(cls, empty, kw);
  if (!out.impl) PyErr_Print();
  Py_DECREF(empty);
  Py_DECREF(kw);
  Py_DECREF(cls);
  return out;
}

void flexflow_config_destroy(flexflow_config_t c) { Py_XDECREF(H(c.impl)); }

flexflow_model_t flexflow_model_create(flexflow_config_t c) {
  flexflow_model_t out{nullptr};
  if (!ensure_init()) return out;
  PyObject* cls = PyObject_GetAttrString(g_module, "FFModel");
  out.impl = PyObject_CallFunctionObjArgs(cls, H(c.impl), nullptr);
  if (!out.impl) PyErr_Print();
  Py_DECREF(cls);
  return out;
}

void flexflow_model_destroy(flexflow_model_t m) { Py_XDECREF(H(m.impl)); }

flexflow_tensor_t flexflow_tensor_create(flexflow_model_t m, int ndims,
                                         const int* dims, const char* dtype) {
  flexflow_tensor_t out{nullptr};
  PyObject* shape = PyTuple_New(ndims);
  for (int i = 0; i < ndims; i++)
    PyTuple_SET_ITEM(shape, i, PyLong_FromLong(dims[i]));
  PyObject* kw = Py_BuildValue("{s:s}", "dtype", dtype ? dtype : "float32");
  out.impl = call(H(m.impl), "create_tensor", Py_BuildValue("(O)", shape), kw);
  Py_DECREF(shape);
  Py_DECREF(kw);
  return out;
}

void flexflow_tensor_destroy(flexflow_tensor_t t) { Py_XDECREF(H(t.impl)); }

flexflow_tensor_t flexflow_model_add_conv2d(
    flexflow_model_t m, flexflow_tensor_t input, int out_channels,
    int kernel_h, int kernel_w, int stride_h, int stride_w, int padding_h,
    int padding_w, int activation, int use_bias, const char* name) {
  flexflow_tensor_t out{nullptr};
  PyObject* kw = Py_BuildValue("{s:s,s:O}", "activation",
                               kActNames[activation & 3], "use_bias",
                               use_bias ? Py_True : Py_False);
  if (name) {
    PyObject* n = PyUnicode_FromString(name);
    PyDict_SetItemString(kw, "name", n);
    Py_DECREF(n);
  }
  out.impl = call(H(m.impl), "conv2d",
                  Py_BuildValue("(Oiiiiiii)", H(input.impl), out_channels,
                                kernel_h, kernel_w, stride_h, stride_w,
                                padding_h, padding_w),
                  kw);
  Py_DECREF(kw);
  return out;
}

flexflow_tensor_t flexflow_model_add_pool2d(
    flexflow_model_t m, flexflow_tensor_t input, int kernel_h, int kernel_w,
    int stride_h, int stride_w, int padding_h, int padding_w, int pool_max,
    const char* name) {
  flexflow_tensor_t out{nullptr};
  PyObject* kw = Py_BuildValue("{s:s}", "pool_type", pool_max ? "max" : "avg");
  if (name) {
    PyObject* n = PyUnicode_FromString(name);
    PyDict_SetItemString(kw, "name", n);
    Py_DECREF(n);
  }
  out.impl = call(H(m.impl), "pool2d",
                  Py_BuildValue("(Oiiiiii)", H(input.impl), kernel_h, kernel_w,
                                stride_h, stride_w, padding_h, padding_w),
                  kw);
  Py_DECREF(kw);
  return out;
}

flexflow_tensor_t flexflow_model_add_dense(flexflow_model_t m,
                                           flexflow_tensor_t input,
                                           int out_dim, int activation,
                                           int use_bias, const char* name) {
  flexflow_tensor_t out{nullptr};
  PyObject* kw = Py_BuildValue("{s:s,s:O}", "activation",
                               kActNames[activation & 3], "use_bias",
                               use_bias ? Py_True : Py_False);
  if (name) {
    PyObject* n = PyUnicode_FromString(name);
    PyDict_SetItemString(kw, "name", n);
    Py_DECREF(n);
  }
  out.impl = call(H(m.impl), "dense",
                  Py_BuildValue("(Oi)", H(input.impl), out_dim), kw);
  Py_DECREF(kw);
  return out;
}

flexflow_tensor_t flexflow_model_add_flat(flexflow_model_t m,
                                          flexflow_tensor_t input,
                                          const char* name) {
  flexflow_tensor_t out{nullptr};
  PyObject* kw = PyDict_New();
  if (name) {
    PyObject* n = PyUnicode_FromString(name);
    PyDict_SetItemString(kw, "name", n);
    Py_DECREF(n);
  }
  out.impl = call(H(m.impl), "flat", Py_BuildValue("(O)", H(input.impl)), kw);
  Py_DECREF(kw);
  return out;
}

flexflow_tensor_t flexflow_model_add_softmax(flexflow_model_t m,
                                             flexflow_tensor_t input,
                                             const char* name) {
  flexflow_tensor_t out{nullptr};
  PyObject* kw = PyDict_New();
  if (name) {
    PyObject* n = PyUnicode_FromString(name);
    PyDict_SetItemString(kw, "name", n);
    Py_DECREF(n);
  }
  out.impl =
      call(H(m.impl), "softmax", Py_BuildValue("(O)", H(input.impl)), kw);
  Py_DECREF(kw);
  return out;
}

flexflow_tensor_t flexflow_model_add_embedding(flexflow_model_t m,
                                               flexflow_tensor_t input,
                                               int num_entries, int out_dim,
                                               int aggr_sum, const char* name) {
  flexflow_tensor_t out{nullptr};
  PyObject* kw = Py_BuildValue("{s:s}", "aggr", aggr_sum ? "sum" : "avg");
  if (name) {
    PyObject* n = PyUnicode_FromString(name);
    PyDict_SetItemString(kw, "name", n);
    Py_DECREF(n);
  }
  out.impl = call(H(m.impl), "embedding",
                  Py_BuildValue("(Oii)", H(input.impl), num_entries, out_dim),
                  kw);
  Py_DECREF(kw);
  return out;
}

flexflow_tensor_t flexflow_model_add_concat(flexflow_model_t m, int n,
                                            const flexflow_tensor_t* inputs,
                                            int axis, const char* name) {
  flexflow_tensor_t out{nullptr};
  PyObject* list = PyList_New(n);
  for (int i = 0; i < n; i++) {
    Py_INCREF(H(inputs[i].impl));
    PyList_SET_ITEM(list, i, H(inputs[i].impl));
  }
  PyObject* kw = PyDict_New();
  if (name) {
    PyObject* nm = PyUnicode_FromString(name);
    PyDict_SetItemString(kw, "name", nm);
    Py_DECREF(nm);
  }
  out.impl = call(H(m.impl), "concat", Py_BuildValue("(Oi)", list, axis), kw);
  Py_DECREF(list);
  Py_DECREF(kw);
  return out;
}

static flexflow_tensor_t binary_op(flexflow_model_t m, const char* method,
                                   flexflow_tensor_t a, flexflow_tensor_t b,
                                   const char* name);

flexflow_tensor_t flexflow_model_add_add(flexflow_model_t m,
                                         flexflow_tensor_t a,
                                         flexflow_tensor_t b,
                                         const char* name) {
  return binary_op(m, "add", a, b, name);
}

static flexflow_tensor_t binary_op(flexflow_model_t m, const char* method,
                                   flexflow_tensor_t a, flexflow_tensor_t b,
                                   const char* name) {
  flexflow_tensor_t out{nullptr};
  if (!a.impl || !b.impl) return out;  // upstream builder failed
  PyObject* kw = PyDict_New();
  if (name) {
    PyObject* nm = PyUnicode_FromString(name);
    PyDict_SetItemString(kw, "name", nm);
    Py_DECREF(nm);
  }
  out.impl = call(H(m.impl), method,
                  Py_BuildValue("(OO)", H(a.impl), H(b.impl)), kw);
  Py_DECREF(kw);
  return out;
}

static flexflow_tensor_t unary_op(flexflow_model_t m, const char* method,
                                  flexflow_tensor_t input, const char* name) {
  flexflow_tensor_t out{nullptr};
  if (!input.impl) return out;  // upstream builder failed
  PyObject* kw = PyDict_New();
  if (name) {
    PyObject* nm = PyUnicode_FromString(name);
    PyDict_SetItemString(kw, "name", nm);
    Py_DECREF(nm);
  }
  out.impl = call(H(m.impl), method, Py_BuildValue("(O)", H(input.impl)), kw);
  Py_DECREF(kw);
  return out;
}

flexflow_tensor_t flexflow_model_add_subtract(flexflow_model_t m,
                                              flexflow_tensor_t a,
                                              flexflow_tensor_t b,
                                              const char* name) {
  return binary_op(m, "subtract", a, b, name);
}
flexflow_tensor_t flexflow_model_add_multiply(flexflow_model_t m,
                                              flexflow_tensor_t a,
                                              flexflow_tensor_t b,
                                              const char* name) {
  return binary_op(m, "multiply", a, b, name);
}
flexflow_tensor_t flexflow_model_add_divide(flexflow_model_t m,
                                            flexflow_tensor_t a,
                                            flexflow_tensor_t b,
                                            const char* name) {
  return binary_op(m, "divide", a, b, name);
}
flexflow_tensor_t flexflow_model_add_relu(flexflow_model_t m,
                                          flexflow_tensor_t input,
                                          const char* name) {
  return unary_op(m, "relu", input, name);
}
flexflow_tensor_t flexflow_model_add_sigmoid(flexflow_model_t m,
                                             flexflow_tensor_t input,
                                             const char* name) {
  return unary_op(m, "sigmoid", input, name);
}
flexflow_tensor_t flexflow_model_add_tanh(flexflow_model_t m,
                                          flexflow_tensor_t input,
                                          const char* name) {
  return unary_op(m, "tanh", input, name);
}
flexflow_tensor_t flexflow_model_add_elu(flexflow_model_t m,
                                         flexflow_tensor_t input,
                                         const char* name) {
  return unary_op(m, "elu", input, name);
}
flexflow_tensor_t flexflow_model_add_exp(flexflow_model_t m,
                                         flexflow_tensor_t input,
                                         const char* name) {
  return unary_op(m, "exp", input, name);
}

flexflow_tensor_t flexflow_model_add_batch_norm(flexflow_model_t m,
                                                flexflow_tensor_t input,
                                                int relu, const char* name) {
  flexflow_tensor_t out{nullptr};
  PyObject* kw = Py_BuildValue("{s:O}", "relu", relu ? Py_True : Py_False);
  if (name) {
    PyObject* nm = PyUnicode_FromString(name);
    PyDict_SetItemString(kw, "name", nm);
    Py_DECREF(nm);
  }
  out.impl = call(H(m.impl), "batch_norm",
                  Py_BuildValue("(O)", H(input.impl)), kw);
  Py_DECREF(kw);
  return out;
}

flexflow_tensor_t flexflow_model_add_dropout(flexflow_model_t m,
                                             flexflow_tensor_t input,
                                             double rate, int seed,
                                             const char* name) {
  flexflow_tensor_t out{nullptr};
  PyObject* kw = Py_BuildValue("{s:i}", "seed", seed);
  if (name) {
    PyObject* nm = PyUnicode_FromString(name);
    PyDict_SetItemString(kw, "name", nm);
    Py_DECREF(nm);
  }
  out.impl = call(H(m.impl), "dropout",
                  Py_BuildValue("(Od)", H(input.impl), rate), kw);
  Py_DECREF(kw);
  return out;
}

flexflow_tensor_t flexflow_model_add_mse_loss(flexflow_model_t m,
                                              flexflow_tensor_t logits,
                                              flexflow_tensor_t labels,
                                              const char* reduction,
                                              const char* name) {
  flexflow_tensor_t out{nullptr};
  PyObject* kw = Py_BuildValue("{s:s}", "reduction",
                               reduction ? reduction : "average");
  if (name) {
    PyObject* nm = PyUnicode_FromString(name);
    PyDict_SetItemString(kw, "name", nm);
    Py_DECREF(nm);
  }
  out.impl = call(H(m.impl), "mse_loss",
                  Py_BuildValue("(OO)", H(logits.impl), H(labels.impl)), kw);
  Py_DECREF(kw);
  return out;
}

int flexflow_model_compile(flexflow_model_t m, const char* optimizer,
                           double lr, const char* loss, const char** metrics,
                           int num_metrics) {
  PyObject* opt = nullptr;
  if (!optimizer || !*optimizer) {
    /* optimizer object bound earlier via flexflow_model_set_*_optimizer */
    opt = PyObject_GetAttrString(H(m.impl), "_c_api_optimizer");
    if (!opt || opt == Py_None) {
      fprintf(stderr, "flexflow_model_compile: no optimizer bound\n");
      Py_XDECREF(opt);
      PyErr_Clear();
      return -1;
    }
  } else {
    PyObject* optcls = PyObject_GetAttrString(
        g_module, strcmp(optimizer, "adam") == 0 ? "AdamOptimizer"
                                                 : "SGDOptimizer");
    PyObject* kw = strcmp(optimizer, "adam") == 0
                       ? Py_BuildValue("{s:d}", "alpha", lr)
                       : Py_BuildValue("{s:d}", "lr", lr);
    PyObject* empty = PyTuple_New(0);
    opt = PyObject_Call(optcls, empty, kw);
    Py_DECREF(empty);
    Py_DECREF(kw);
    Py_DECREF(optcls);
  }
  if (!opt) { PyErr_Print(); return -1; }
  PyObject* mlist = PyList_New(num_metrics);
  for (int i = 0; i < num_metrics; i++)
    PyList_SET_ITEM(mlist, i, PyUnicode_FromString(metrics[i]));
  PyObject* res = call(H(m.impl), "compile",
                       Py_BuildValue("(OsO)", opt, loss, mlist));
  Py_DECREF(opt);
  Py_DECREF(mlist);
  if (!res) return -1;
  Py_DECREF(res);
  return 0;
}

int flexflow_model_init_layers(flexflow_model_t m) {
  PyObject* res = call(H(m.impl), "init_layers", PyTuple_New(0));
  if (!res) return -1;
  Py_DECREF(res);
  return 0;
}

int flexflow_model_set_input_f32(flexflow_model_t m, flexflow_tensor_t t,
                                 const float* data, int64_t count) {
  // reshape to the tensor's *native* dims: C callers pass reference-order
  // data for 4-D (N,C,H,W) — convert via numpy transpose
  PyObject* tensor = H(t.impl);
  PyObject* dims_obj = PyObject_GetAttrString(tensor, "dims");
  int nd = (int)PyTuple_Size(dims_obj);
  std::vector<int> dims(nd);
  for (int i = 0; i < nd; i++)
    dims[i] = (int)PyLong_AsLong(PyTuple_GetItem(dims_obj, i));
  Py_DECREF(dims_obj);
  std::vector<int> cdims(dims);
  if (nd == 4) {  // caller provides N,C,H,W; tensor dims are N,H,W,C
    cdims[1] = dims[3]; cdims[2] = dims[1]; cdims[3] = dims[2];
  }
  PyObject* arr = np_array(data, count, cdims.data(), nd, 'f');
  if (!arr) return -1;
  if (nd == 4) {
    PyObject* tr = call(arr, "transpose", Py_BuildValue("(iiii)", 0, 2, 3, 1));
    Py_DECREF(arr);
    arr = tr;
    if (!arr) return -1;
  }
  return stage_input(m, tensor, arr);
}

int flexflow_model_set_input_i32(flexflow_model_t m, flexflow_tensor_t t,
                                 const int32_t* data, int64_t count) {
  PyObject* tensor = H(t.impl);
  PyObject* dims_obj = PyObject_GetAttrString(tensor, "dims");
  int nd = (int)PyTuple_Size(dims_obj);
  std::vector<int> dims(nd);
  for (int i = 0; i < nd; i++)
    dims[i] = (int)PyLong_AsLong(PyTuple_GetItem(dims_obj, i));
  Py_DECREF(dims_obj);
  PyObject* arr = np_array(data, count, dims.data(), nd, 'i');
  if (!arr) return -1;
  return stage_input(m, tensor, arr);
}

static int set_label(flexflow_model_t m, PyObject* arr) {
  if (!arr) return -1;
  PyObject_SetAttrString(H(m.impl), "_c_api_label", arr);
  Py_DECREF(arr);
  return flush_batch_if_ready(m);
}

int flexflow_model_set_label_i32(flexflow_model_t m, const int32_t* data,
                                 int64_t count) {
  PyObject* model = H(m.impl);
  PyObject* lt = PyObject_GetAttrString(model, "label_tensor");
  PyObject* dims_obj = PyObject_GetAttrString(lt, "dims");
  int nd = (int)PyTuple_Size(dims_obj);
  std::vector<int> dims(nd);
  for (int i = 0; i < nd; i++)
    dims[i] = (int)PyLong_AsLong(PyTuple_GetItem(dims_obj, i));
  Py_DECREF(dims_obj);
  Py_DECREF(lt);
  return set_label(m, np_array(data, count, dims.data(), nd, 'i'));
}

int flexflow_model_set_label_f32(flexflow_model_t m, const float* data,
                                 int64_t count) {
  PyObject* model = H(m.impl);
  PyObject* lt = PyObject_GetAttrString(model, "label_tensor");
  PyObject* dims_obj = PyObject_GetAttrString(lt, "dims");
  int nd = (int)PyTuple_Size(dims_obj);
  std::vector<int> dims(nd);
  for (int i = 0; i < nd; i++)
    dims[i] = (int)PyLong_AsLong(PyTuple_GetItem(dims_obj, i));
  Py_DECREF(dims_obj);
  Py_DECREF(lt);
  return set_label(m, np_array(data, count, dims.data(), nd, 'f'));
}

static int simple_call(flexflow_model_t m, const char* method) {
  PyObject* res = call(H(m.impl), method, PyTuple_New(0));
  if (!res) return -1;
  Py_DECREF(res);
  return 0;
}

int flexflow_model_forward(flexflow_model_t m) {
  if (flush_batch_if_ready(m) != 0) return -1;
  return simple_call(m, "forward");
}
int flexflow_model_zero_gradients(flexflow_model_t m) {
  return simple_call(m, "zero_gradients");
}
int flexflow_model_backward(flexflow_model_t m) {
  return simple_call(m, "backward");
}
int flexflow_model_update(flexflow_model_t m) {
  return simple_call(m, "update");
}
int flexflow_model_sync(flexflow_model_t m) { return simple_call(m, "sync"); }

void flexflow_model_reset_metrics(flexflow_model_t m) {
  simple_call(m, "reset_metrics");
}

double flexflow_model_get_accuracy(flexflow_model_t m, int64_t* train_all,
                                   int64_t* train_correct) {
  PyObject* pm = call(H(m.impl), "get_metrics", PyTuple_New(0));
  if (!pm) return -1.0;
  PyObject* acc = PyObject_GetAttrString(pm, "accuracy");
  PyObject* ta = PyObject_GetAttrString(pm, "train_all");
  PyObject* tc = PyObject_GetAttrString(pm, "train_correct");
  double result = acc ? PyFloat_AsDouble(acc) : -1.0;
  if (train_all && ta) *train_all = PyLong_AsLongLong(ta);
  if (train_correct && tc) *train_correct = PyLong_AsLongLong(tc);
  Py_XDECREF(acc); Py_XDECREF(ta); Py_XDECREF(tc); Py_DECREF(pm);
  return result;
}

int flexflow_model_train_iteration(flexflow_model_t m) {
  if (flush_batch_if_ready(m) != 0) return -1;
  return simple_call(m, "train_iteration");
}

double flexflow_model_get_metric(flexflow_model_t m, const char* name) {
  PyObject* pm = call(H(m.impl), "get_metrics", PyTuple_New(0));
  if (!pm) return -1.0;
  PyObject* v = PyObject_GetAttrString(pm, name);
  double result = v ? PyFloat_AsDouble(v) : -1.0;
  if (PyErr_Occurred()) { PyErr_Print(); result = -1.0; }
  Py_XDECREF(v);
  Py_DECREF(pm);
  return result;
}

int64_t flexflow_parameter_get_volume(flexflow_model_t m, const char* op_name,
                                      const char* weight_name) {
  PyObject* arr = call(H(m.impl), "get_parameter",
                       Py_BuildValue("(ss)", op_name, weight_name));
  if (!arr) return -1;
  PyObject* size = PyObject_GetAttrString(arr, "size");
  int64_t n = size ? PyLong_AsLongLong(size) : -1;
  if (PyErr_Occurred()) {
    PyErr_Print();
    n = -1;
  }
  Py_XDECREF(size);
  Py_DECREF(arr);
  return n;
}

int flexflow_model_get_parameter_f32(flexflow_model_t m, const char* op_name,
                                     const char* weight_name, float* out,
                                     int64_t count) {
  PyObject* arr = call(H(m.impl), "get_parameter",
                       Py_BuildValue("(ss)", op_name, weight_name));
  if (!arr) return -1;
  PyObject* flat = call(arr, "astype", Py_BuildValue("(s)", "float32"));
  Py_DECREF(arr);
  if (!flat) return -1;
  PyObject* rav = call(flat, "ravel", PyTuple_New(0));
  Py_DECREF(flat);
  if (!rav) return -1;
  PyObject* lst = call(rav, "tolist", PyTuple_New(0));
  Py_DECREF(rav);
  if (!lst) return -1;
  int64_t n = PyList_Size(lst);
  int rc = 0;
  if (n != count) {
    rc = -1;
  } else {
    for (int64_t i = 0; i < n; i++)
      out[i] = (float)PyFloat_AsDouble(PyList_GET_ITEM(lst, i));
  }
  Py_DECREF(lst);
  return rc;
}

int flexflow_model_set_parameter_f32(flexflow_model_t m, const char* op_name,
                                     const char* weight_name,
                                     const float* data, int64_t count) {
  PyObject* arr_flat = np_array(data, count, nullptr, 1, 'f');
  if (!arr_flat) return -1;
  // reshape to the current parameter's shape
  PyObject* cur = call(H(m.impl), "get_parameter",
                       Py_BuildValue("(ss)", op_name, weight_name));
  if (!cur) { Py_DECREF(arr_flat); return -1; }
  PyObject* shape = PyObject_GetAttrString(cur, "shape");
  Py_DECREF(cur);
  if (!shape) {
    PyErr_Print();
    Py_DECREF(arr_flat);
    return -1;
  }
  PyObject* arr = call(arr_flat, "reshape", Py_BuildValue("(O)", shape));
  Py_DECREF(shape);
  Py_DECREF(arr_flat);
  if (!arr) return -1;
  PyObject* res = call(H(m.impl), "set_parameter",
                       Py_BuildValue("(ssO)", op_name, weight_name, arr));
  Py_DECREF(arr);
  if (!res) return -1;
  Py_DECREF(res);
  return 0;
}

int flexflow_config_import_strategy(flexflow_config_t c, const char* path) {
  PyObject* p = PyUnicode_FromString(path);
  int rc = PyObject_SetAttrString(H(c.impl), "import_strategy_file", p);
  Py_DECREF(p);
  return rc;
}

int flexflow_model_export_strategy(flexflow_model_t m, const char* path) {
  PyObject* strategies = call(H(m.impl), "get_strategies", PyTuple_New(0));
  if (!strategies) return -1;
  PyObject* fn = PyObject_GetAttrString(g_module, "save_strategies_to_file");
  if (!fn) { Py_DECREF(strategies); PyErr_Print(); return -1; }
  PyObject* res = PyObject_CallFunction(fn, "sO", path, strategies);
  Py_DECREF(fn);
  Py_DECREF(strategies);
  if (!res) { PyErr_Print(); return -1; }
  Py_DECREF(res);
  return 0;
}

int flexflow_model_save(flexflow_model_t m, const char* path) {
  PyObject* res = call(H(m.impl), "save", Py_BuildValue("(s)", path));
  if (!res) return -1;
  Py_DECREF(res);
  return 0;
}

int flexflow_model_load(flexflow_model_t m, const char* path) {
  PyObject* res = call(H(m.impl), "load", Py_BuildValue("(s)", path));
  if (!res) return -1;
  Py_DECREF(res);
  return 0;
}

int flexflow_tensor_get_dims(flexflow_tensor_t t, int* dims) {
  PyObject* dims_obj = PyObject_GetAttrString(H(t.impl), "dims");
  if (!dims_obj) return -1;
  int nd = (int)PyTuple_Size(dims_obj);
  for (int i = 0; i < nd && i < 8; i++)
    dims[i] = (int)PyLong_AsLong(PyTuple_GetItem(dims_obj, i));
  Py_DECREF(dims_obj);
  return nd;
}

/* ====================================================================
 * Extended surface (reference parity: python/flexflow_c.h:27-718)
 * ==================================================================== */

/* ---- config accessors ---------------------------------------------- */

int flexflow_config_parse_args(flexflow_config_t c, int argc, char** argv) {
  PyObject* list = PyList_New(argc);
  for (int i = 0; i < argc; i++)
    PyList_SET_ITEM(list, i, PyUnicode_FromString(argv[i]));
  PyObject* res = call(H(c.impl), "parse_args", Py_BuildValue("(O)", list));
  Py_DECREF(list);
  if (!res) return -1;
  Py_DECREF(res);
  return 0;
}

int flexflow_config_parse_args_default(flexflow_config_t c) {
  PyObject* res = call(H(c.impl), "parse_args", PyTuple_New(0));
  if (!res) return -1;
  Py_DECREF(res);
  return 0;
}

static int config_get_int(flexflow_config_t c, const char* attr) {
  PyObject* v = PyObject_GetAttrString(H(c.impl), attr);
  if (!v) { PyErr_Print(); return -1; }
  int out = (int)PyLong_AsLong(v);
  Py_DECREF(v);
  return out;
}

int flexflow_config_get_batch_size(flexflow_config_t c) {
  return config_get_int(c, "batch_size");
}
int flexflow_config_get_epochs(flexflow_config_t c) {
  return config_get_int(c, "epochs");
}
int flexflow_config_get_num_nodes(flexflow_config_t c) {
  return config_get_int(c, "num_nodes");
}
int flexflow_config_get_workers_per_node(flexflow_config_t c) {
  return config_get_int(c, "workers_per_node");
}

/* ---- optimizer objects --------------------------------------------- */

static void* make_object(const char* cls_name, PyObject* kw) {
  if (!ensure_init()) { Py_XDECREF(kw); return nullptr; }
  PyObject* cls = PyObject_GetAttrString(g_module, cls_name);
  if (!cls) { PyErr_Print(); Py_XDECREF(kw); return nullptr; }
  PyObject* empty = PyTuple_New(0);
  PyObject* obj = PyObject_Call(cls, empty, kw);
  if (!obj) PyErr_Print();
  Py_DECREF(empty);
  Py_XDECREF(kw);
  Py_DECREF(cls);
  return obj;
}

flexflow_sgd_optimizer_t flexflow_sgd_optimizer_create(
    flexflow_model_t m, double lr, double momentum, int nesterov,
    double weight_decay) {
  (void)m;  /* reference binds the model at create; ours binds at compile */
  flexflow_sgd_optimizer_t out{nullptr};
  out.impl = make_object("SGDOptimizer",
      Py_BuildValue("{s:d,s:d,s:O,s:d}", "lr", lr, "momentum", momentum,
                    "nesterov", nesterov ? Py_True : Py_False,
                    "weight_decay", weight_decay));
  return out;
}

void flexflow_sgd_optimizer_destroy(flexflow_sgd_optimizer_t o) {
  Py_XDECREF(H(o.impl));
}

void flexflow_sgd_optimizer_set_lr(flexflow_sgd_optimizer_t o, double lr) {
  PyObject* v = PyFloat_FromDouble(lr);
  PyObject_SetAttrString(H(o.impl), "lr", v);
  Py_DECREF(v);
}

flexflow_adam_optimizer_t flexflow_adam_optimizer_create(
    flexflow_model_t m, double alpha, double beta1, double beta2,
    double weight_decay, double epsilon) {
  (void)m;
  flexflow_adam_optimizer_t out{nullptr};
  out.impl = make_object("AdamOptimizer",
      Py_BuildValue("{s:d,s:d,s:d,s:d,s:d}", "alpha", alpha, "beta1", beta1,
                    "beta2", beta2, "weight_decay", weight_decay,
                    "epsilon", epsilon));
  return out;
}

void flexflow_adam_optimizer_destroy(flexflow_adam_optimizer_t o) {
  Py_XDECREF(H(o.impl));
}

void flexflow_adam_optimizer_set_lr(flexflow_adam_optimizer_t o, double lr) {
  PyObject* v = PyFloat_FromDouble(lr);
  PyObject_SetAttrString(H(o.impl), "alpha", v);
  Py_DECREF(v);
}

static int set_model_optimizer(flexflow_model_t m, void* opt) {
  if (!opt) return -1;
  return PyObject_SetAttrString(H(m.impl), "_c_api_optimizer",
                                H(opt)) == 0 ? 0 : -1;
}

int flexflow_model_set_sgd_optimizer(flexflow_model_t m,
                                     flexflow_sgd_optimizer_t o) {
  return set_model_optimizer(m, o.impl);
}

int flexflow_model_set_adam_optimizer(flexflow_model_t m,
                                      flexflow_adam_optimizer_t o) {
  return set_model_optimizer(m, o.impl);
}

/* ---- initializer objects ------------------------------------------- */

flexflow_initializer_t flexflow_initializer_create_null(void) {
  flexflow_initializer_t out{nullptr};  /* null = op default initializer */
  return out;
}

flexflow_glorot_uniform_initializer_t
flexflow_glorot_uniform_initializer_create(int seed) {
  flexflow_glorot_uniform_initializer_t out{nullptr};
  out.impl = make_object("GlorotUniform", Py_BuildValue("{s:i}", "seed", seed));
  return out;
}
void flexflow_glorot_uniform_initializer_destroy(
    flexflow_glorot_uniform_initializer_t i) { Py_XDECREF(H(i.impl)); }

flexflow_zero_initializer_t flexflow_zero_initializer_create(void) {
  flexflow_zero_initializer_t out{nullptr};
  out.impl = make_object("ZeroInitializer", nullptr);
  return out;
}
void flexflow_zero_initializer_destroy(flexflow_zero_initializer_t i) {
  Py_XDECREF(H(i.impl));
}

flexflow_uniform_initializer_t flexflow_uniform_initializer_create(
    int seed, float min_val, float max_val) {
  flexflow_uniform_initializer_t out{nullptr};
  out.impl = make_object("UniformInitializer",
      Py_BuildValue("{s:i,s:d,s:d}", "seed", seed, "min_val",
                    (double)min_val, "max_val", (double)max_val));
  return out;
}
void flexflow_uniform_initializer_destroy(flexflow_uniform_initializer_t i) {
  Py_XDECREF(H(i.impl));
}

flexflow_norm_initializer_t flexflow_norm_initializer_create(
    int seed, float mean, float stddev) {
  flexflow_norm_initializer_t out{nullptr};
  out.impl = make_object("NormInitializer",
      Py_BuildValue("{s:i,s:d,s:d}", "seed", seed, "mean", (double)mean,
                    "stddev", (double)stddev));
  return out;
}
void flexflow_norm_initializer_destroy(flexflow_norm_initializer_t i) {
  Py_XDECREF(H(i.impl));
}

/* ---- builder variants with initializer handles --------------------- */

static void kw_set_init(PyObject* kw, const char* key, void* init) {
  if (init) PyDict_SetItemString(kw, key, H(init));
}

flexflow_tensor_t flexflow_model_add_dense_v2(
    flexflow_model_t m, flexflow_tensor_t input, int out_dim, int activation,
    int use_bias, flexflow_initializer_t kernel_init,
    flexflow_initializer_t bias_init, const char* name) {
  flexflow_tensor_t out{nullptr};
  PyObject* kw = Py_BuildValue("{s:s,s:O}", "activation",
                               kActNames[activation & 3], "use_bias",
                               use_bias ? Py_True : Py_False);
  kw_set_init(kw, "kernel_initializer", kernel_init.impl);
  kw_set_init(kw, "bias_initializer", bias_init.impl);
  if (name) {
    PyObject* n = PyUnicode_FromString(name);
    PyDict_SetItemString(kw, "name", n);
    Py_DECREF(n);
  }
  out.impl = call(H(m.impl), "dense",
                  Py_BuildValue("(Oi)", H(input.impl), out_dim), kw);
  Py_DECREF(kw);
  return out;
}

flexflow_tensor_t flexflow_model_add_conv2d_v2(
    flexflow_model_t m, flexflow_tensor_t input, int out_channels,
    int kernel_h, int kernel_w, int stride_h, int stride_w, int padding_h,
    int padding_w, int activation, int use_bias,
    flexflow_initializer_t kernel_init, flexflow_initializer_t bias_init,
    const char* name) {
  flexflow_tensor_t out{nullptr};
  PyObject* kw = Py_BuildValue("{s:s,s:O}", "activation",
                               kActNames[activation & 3], "use_bias",
                               use_bias ? Py_True : Py_False);
  kw_set_init(kw, "kernel_initializer", kernel_init.impl);
  kw_set_init(kw, "bias_initializer", bias_init.impl);
  if (name) {
    PyObject* n = PyUnicode_FromString(name);
    PyDict_SetItemString(kw, "name", n);
    Py_DECREF(n);
  }
  out.impl = call(H(m.impl), "conv2d",
                  Py_BuildValue("(Oiiiiiii)", H(input.impl), out_channels,
                                kernel_h, kernel_w, stride_h, stride_w,
                                padding_h, padding_w),
                  kw);
  Py_DECREF(kw);
  return out;
}

flexflow_tensor_t flexflow_model_add_expert_mlp(
    flexflow_model_t m, flexflow_tensor_t input, int num_experts,
    int hidden_size, double capacity_factor, const char* name) {
  flexflow_tensor_t out{nullptr};
  PyObject* kw = Py_BuildValue("{s:d}", "capacity_factor", capacity_factor);
  if (name) {
    PyObject* n = PyUnicode_FromString(name);
    PyDict_SetItemString(kw, "name", n);
    Py_DECREF(n);
  }
  out.impl = call(H(m.impl), "expert_mlp",
                  Py_BuildValue("(Oii)", H(input.impl), num_experts,
                                hidden_size),
                  kw);
  Py_DECREF(kw);
  return out;
}

/* ---- NetConfig ------------------------------------------------------ */

flexflow_net_config_t flexflow_net_config_create(void) {
  flexflow_net_config_t out{nullptr};
  const char* p = getenv("FF_DATASET");
  out.impl = PyUnicode_FromString(p ? p : "");
  return out;
}
void flexflow_net_config_destroy(flexflow_net_config_t c) {
  Py_XDECREF(H(c.impl));
}
const char* flexflow_net_config_get_dataset_path(flexflow_net_config_t c) {
  return c.impl ? PyUnicode_AsUTF8(H(c.impl)) : "";
}

/* ---- deferred-shape (functional) builders --------------------------- */

static flexflow_op_t deferred_op(const char* method, PyObject* args,
                                 PyObject* kw, const char* name) {
  flexflow_op_t out{nullptr};
  PyObject* d = PyDict_New();
  PyObject* me = PyUnicode_FromString(method);
  PyDict_SetItemString(d, "_deferred", me);
  Py_DECREF(me);
  PyDict_SetItemString(d, "args", args);
  PyDict_SetItemString(d, "kwargs", kw);
  if (name) {
    PyObject* n = PyUnicode_FromString(name);
    PyDict_SetItemString(kw, "name", n);
    Py_DECREF(n);
  }
  Py_DECREF(args);
  Py_DECREF(kw);
  out.impl = d;
  return out;
}

flexflow_op_t flexflow_model_add_conv2d_no_inout(
    flexflow_model_t m, int out_channels, int kernel_h, int kernel_w,
    int stride_h, int stride_w, int padding_h, int padding_w, int activation,
    int use_bias, const char* name) {
  (void)m;
  return deferred_op("conv2d",
      Py_BuildValue("(iiiiiii)", out_channels, kernel_h, kernel_w, stride_h,
                    stride_w, padding_h, padding_w),
      Py_BuildValue("{s:s,s:O}", "activation", kActNames[activation & 3],
                    "use_bias", use_bias ? Py_True : Py_False),
      name);
}

flexflow_op_t flexflow_model_add_dense_no_inout(
    flexflow_model_t m, int out_dim, int activation, int use_bias,
    const char* name) {
  (void)m;
  return deferred_op("dense", Py_BuildValue("(i)", out_dim),
      Py_BuildValue("{s:s,s:O}", "activation", kActNames[activation & 3],
                    "use_bias", use_bias ? Py_True : Py_False),
      name);
}

flexflow_op_t flexflow_model_add_pool2d_no_inout(
    flexflow_model_t m, int kernel_h, int kernel_w, int stride_h,
    int stride_w, int padding_h, int padding_w, int pool_max,
    const char* name) {
  (void)m;
  return deferred_op("pool2d",
      Py_BuildValue("(iiiiii)", kernel_h, kernel_w, stride_h, stride_w,
                    padding_h, padding_w),
      Py_BuildValue("{s:s}", "pool_type", pool_max ? "max" : "avg"), name);
}

flexflow_op_t flexflow_model_add_flat_no_inout(flexflow_model_t m,
                                               const char* name) {
  (void)m;
  return deferred_op("flat", PyTuple_New(0), PyDict_New(), name);
}

flexflow_tensor_t flexflow_op_init_inout(flexflow_op_t op, flexflow_model_t m,
                                         flexflow_tensor_t input) {
  flexflow_tensor_t out{nullptr};
  PyObject* d = H(op.impl);
  if (!d || !PyDict_Check(d)) return out;
  PyObject* method = PyDict_GetItemString(d, "_deferred");
  PyObject* args = PyDict_GetItemString(d, "args");
  PyObject* kw = PyDict_GetItemString(d, "kwargs");
  if (!method || !args) return out;
  Py_ssize_t n = PyTuple_Size(args);
  PyObject* full = PyTuple_New(n + 1);
  Py_INCREF(H(input.impl));
  PyTuple_SET_ITEM(full, 0, H(input.impl));
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject* it = PyTuple_GetItem(args, i);
    Py_INCREF(it);
    PyTuple_SET_ITEM(full, i + 1, it);
  }
  out.impl = call(H(m.impl), PyUnicode_AsUTF8(method), full, kw);
  if (out.impl) {
    PyDict_SetItemString(d, "output", H(out.impl));
    PyObject* ops = PyObject_GetAttrString(H(m.impl), "ops");
    if (ops) {
      PyObject* last = PyList_GetItem(ops, PyList_Size(ops) - 1);
      if (last) PyDict_SetItemString(d, "op", last);
      Py_DECREF(ops);
    }
  }
  return out;
}

int flexflow_op_add_to_model(flexflow_op_t op, flexflow_model_t m) {
  (void)m;  /* ops join the graph at creation in this core */
  return (op.impl && (!PyDict_Check(H(op.impl)) ||
                      PyDict_GetItemString(H(op.impl), "op"))) ? 0 : -1;
}

int flexflow_op_init(flexflow_op_t op, flexflow_model_t m) {
  (void)op;  /* per-op init happens inside model init_layers */
  (void)m;
  return 0;
}

int flexflow_op_forward(flexflow_op_t op, flexflow_model_t m) {
  (void)op;  /* the fused step runs the whole graph; a standalone op
                forward maps to the staged driver's forward */
  PyObject* res = call(H(m.impl), "forward", PyTuple_New(0));
  if (!res) return -1;
  Py_DECREF(res);
  return 0;
}

/* ---- op / parameter handles ----------------------------------------- */

static PyObject* resolve_op(flexflow_op_t op) {
  PyObject* h = H(op.impl);
  if (h && PyDict_Check(h)) return PyDict_GetItemString(h, "op");
  return h;
}

int flexflow_model_get_num_layers(flexflow_model_t m) {
  PyObject* ops = PyObject_GetAttrString(H(m.impl), "ops");
  if (!ops) return -1;
  int n = (int)PyList_Size(ops);
  Py_DECREF(ops);
  return n;
}

flexflow_op_t flexflow_model_get_layer_by_id(flexflow_model_t m, int id) {
  flexflow_op_t out{nullptr};
  PyObject* ops = PyObject_GetAttrString(H(m.impl), "ops");
  if (!ops) return out;
  PyObject* op = PyList_GetItem(ops, id);  /* borrowed */
  if (op) { Py_INCREF(op); out.impl = op; }
  else PyErr_Clear();
  Py_DECREF(ops);
  return out;
}

void flexflow_op_destroy(flexflow_op_t op) { Py_XDECREF(H(op.impl)); }

static flexflow_tensor_t op_tensor_by_id(flexflow_op_t op, const char* attr,
                                         int id) {
  flexflow_tensor_t out{nullptr};
  PyObject* o = resolve_op(op);
  if (!o) return out;
  PyObject* lst = PyObject_GetAttrString(o, attr);
  if (!lst) { PyErr_Print(); return out; }
  PyObject* t = PySequence_GetItem(lst, id);  /* new ref */
  if (!t) PyErr_Clear();
  out.impl = t;
  Py_DECREF(lst);
  return out;
}

flexflow_tensor_t flexflow_op_get_input_by_id(flexflow_op_t op, int id) {
  return op_tensor_by_id(op, "inputs", id);
}

flexflow_tensor_t flexflow_op_get_output_by_id(flexflow_op_t op, int id) {
  PyObject* h = H(op.impl);
  if (h && PyDict_Check(h)) {  /* deferred handle: cached output tensor */
    flexflow_tensor_t out{nullptr};
    PyObject* t = PyDict_GetItemString(h, "output");
    if (t && id == 0) { Py_INCREF(t); out.impl = t; }
    return out;
  }
  return op_tensor_by_id(op, "outputs", id);
}

flexflow_parameter_t flexflow_op_get_parameter_by_id(flexflow_op_t op,
                                                     int id) {
  flexflow_parameter_t out{nullptr};
  PyObject* o = resolve_op(op);
  if (!o) return out;
  PyObject* ws = PyObject_GetAttrString(o, "weights");
  if (!ws) { PyErr_Print(); return out; }
  PyObject* w = PySequence_GetItem(ws, id);
  if (!w) PyErr_Clear();
  out.impl = w;
  Py_DECREF(ws);
  return out;
}

flexflow_parameter_t flexflow_model_get_parameter_by_id(flexflow_model_t m,
                                                        int id) {
  flexflow_parameter_t out{nullptr};
  PyObject* ops = PyObject_GetAttrString(H(m.impl), "ops");
  if (!ops) return out;
  int seen = 0;
  for (Py_ssize_t i = 0; i < PyList_Size(ops) && !out.impl; i++) {
    PyObject* ws = PyObject_GetAttrString(PyList_GetItem(ops, i), "weights");
    if (!ws) continue;
    int nw = (int)PySequence_Size(ws);
    if (id < seen + nw) out.impl = PySequence_GetItem(ws, id - seen);
    seen += nw;
    Py_DECREF(ws);
  }
  Py_DECREF(ops);
  return out;
}

void flexflow_parameter_destroy(flexflow_parameter_t p) {
  Py_XDECREF(H(p.impl));
}

int64_t flexflow_parameter_get_volume_v2(flexflow_parameter_t p) {
  PyObject* v = call(H(p.impl), "volume", PyTuple_New(0));
  if (!v) return -1;
  int64_t out = PyLong_AsLongLong(v);
  Py_DECREF(v);
  return out;
}

/* (owner_op.model, owner_op.name, param.name) → get/set via model API */
static PyObject* param_model(PyObject* p) {
  PyObject* op = PyObject_GetAttrString(p, "owner_op");
  if (!op) return nullptr;
  PyObject* model = PyObject_GetAttrString(op, "model");
  Py_DECREF(op);
  return model;
}

static int param_names(PyObject* p, PyObject** op_name, PyObject** w_name) {
  PyObject* op = PyObject_GetAttrString(p, "owner_op");
  if (!op) return -1;
  *op_name = PyObject_GetAttrString(op, "name");
  Py_DECREF(op);
  *w_name = PyObject_GetAttrString(p, "name");
  return (*op_name && *w_name) ? 0 : -1;
}

int flexflow_parameter_get_weights_float(flexflow_parameter_t p, float* out,
                                         int64_t count) {
  PyObject* model = param_model(H(p.impl));
  PyObject *opn = nullptr, *wn = nullptr;
  if (!model || param_names(H(p.impl), &opn, &wn) != 0) {
    Py_XDECREF(model);
    return -1;
  }
  PyObject* arr = call(model, "get_parameter",
                       Py_BuildValue("(OO)", opn, wn));
  Py_DECREF(model); Py_DECREF(opn); Py_DECREF(wn);
  if (!arr) return -1;
  PyObject* flat = call(arr, "ravel", PyTuple_New(0));
  Py_DECREF(arr);
  if (!flat) return -1;
  PyObject* f32 = call(flat, "astype", Py_BuildValue("(s)", "float32"));
  Py_DECREF(flat);
  if (!f32) return -1;
  PyObject* bytes = call(f32, "tobytes", PyTuple_New(0));
  Py_DECREF(f32);
  if (!bytes) return -1;
  int64_t have = (int64_t)(PyBytes_Size(bytes) / sizeof(float));
  int ok = -1;
  if (have <= count) {
    memcpy(out, PyBytes_AsString(bytes), (size_t)have * sizeof(float));
    ok = 0;
  }
  Py_DECREF(bytes);
  return ok;
}

int flexflow_parameter_set_weights_float(flexflow_parameter_t p,
                                         const float* data, int64_t count) {
  PyObject* model = param_model(H(p.impl));
  PyObject *opn = nullptr, *wn = nullptr;
  if (!model || param_names(H(p.impl), &opn, &wn) != 0) {
    Py_XDECREF(model);
    return -1;
  }
  PyObject* dims = PyObject_GetAttrString(H(p.impl), "dims");
  int nd = dims ? (int)PyTuple_Size(dims) : 1;
  std::vector<int> cdims(nd, (int)count);
  for (int i = 0; dims && i < nd; i++)
    cdims[i] = (int)PyLong_AsLong(PyTuple_GetItem(dims, i));
  Py_XDECREF(dims);
  PyObject* arr = np_array(data, count, cdims.data(), nd, 'f');
  int ok = -1;
  if (arr) {
    PyObject* res = call(model, "set_parameter",
                         Py_BuildValue("(OOO)", opn, wn, arr));
    if (res) { ok = 0; Py_DECREF(res); }
    Py_DECREF(arr);
  }
  Py_DECREF(model); Py_DECREF(opn); Py_DECREF(wn);
  return ok;
}

/* ---- label tensor / layers / prefetch ------------------------------- */

flexflow_tensor_t flexflow_model_get_label_tensor(flexflow_model_t m) {
  flexflow_tensor_t out{nullptr};
  out.impl = PyObject_GetAttrString(H(m.impl), "label_tensor");
  if (!out.impl) PyErr_Clear();
  return out;
}

void flexflow_model_print_layers(flexflow_model_t m, int id) {
  PyObject* ops = PyObject_GetAttrString(H(m.impl), "ops");
  if (!ops) return;
  for (Py_ssize_t i = 0; i < PyList_Size(ops); i++) {
    if (id >= 0 && i != id) continue;
    PyObject* r = PyObject_Repr(PyList_GetItem(ops, i));
    if (r) {
      printf("layer[%zd]: %s\n", i, PyUnicode_AsUTF8(r));
      Py_DECREF(r);
    }
  }
  Py_DECREF(ops);
}

int flexflow_model_prefetch(flexflow_model_t m) {
  (void)m;  /* device_put of the staged batch is already async */
  return 0;
}

/* ---- perf metrics handle -------------------------------------------- */

flexflow_perf_metrics_t flexflow_model_get_perf_metrics(flexflow_model_t m) {
  flexflow_perf_metrics_t out{nullptr};
  out.impl = call(H(m.impl), "get_metrics", PyTuple_New(0));
  return out;
}

void flexflow_per_metrics_destroy(flexflow_perf_metrics_t p) {
  Py_XDECREF(H(p.impl));
}

float flexflow_per_metrics_get_accuracy(flexflow_perf_metrics_t p) {
  PyObject* acc = PyObject_GetAttrString(H(p.impl), "accuracy");
  if (!acc) { PyErr_Print(); return -1.0f; }
  float out = (float)PyFloat_AsDouble(acc);
  Py_DECREF(acc);
  return out;
}

int flexflow_model_compute_metrics(flexflow_model_t m) {
  /* metrics accumulate on-device inside the fused step; draining folds
     them into the host PerfMetrics (reference: UPDATE_METRICS_TASK) */
  PyObject* res = call(H(m.impl), "_drain_metrics", PyTuple_New(0));
  if (!res) return -1;
  Py_DECREF(res);
  return 0;
}

/* ---- tracing + timing ----------------------------------------------- */

void flexflow_begin_trace(flexflow_model_t m, int trace_id) {
  (void)m; (void)trace_id;  /* XLA traces the fused step once at jit;
                               replay is automatic (≈ Legion begin_trace) */
}

void flexflow_end_trace(flexflow_model_t m, int trace_id) {
  (void)m; (void)trace_id;
}

double flexflow_get_current_time(flexflow_model_t m) {
  (void)m;
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return ts.tv_sec * 1e6 + ts.tv_nsec * 1e-3;  /* microseconds */
}

/* ---- raw-ptr attach + inline map ------------------------------------ */

static PyObject* model_dict_attr(PyObject* model, const char* attr) {
  PyObject* d = PyObject_GetAttrString(model, attr);
  if (!d || d == Py_None) {
    Py_XDECREF(d);
    PyErr_Clear();
    d = PyDict_New();
    PyObject_SetAttrString(model, attr, d);
  }
  return d;  /* new ref */
}

int flexflow_tensor_attach_raw_ptr(flexflow_model_t m, flexflow_tensor_t t,
                                   void* ptr, int64_t count, int is_float) {
  /* zero-copy: wrap the caller's memory as a numpy view shaped
     (-1, *tensor.dims[1:]) — the host-resident full dataset */
  PyObject* mv = PyMemoryView_FromMemory(
      (char*)ptr, count * 4, PyBUF_WRITE);
  if (!mv) { PyErr_Print(); return -1; }
  PyObject* arr = call(g_np, "frombuffer", Py_BuildValue("(O)", mv),
                       Py_BuildValue("{s:s}", "dtype",
                                     is_float ? "float32" : "int32"));
  Py_DECREF(mv);
  if (!arr) return -1;
  PyObject* dims_obj = PyObject_GetAttrString(H(t.impl), "dims");
  if (dims_obj) {
    Py_ssize_t nd = PyTuple_Size(dims_obj);
    PyObject* shape = PyTuple_New(nd);
    PyTuple_SET_ITEM(shape, 0, PyLong_FromLong(-1));
    for (Py_ssize_t i = 1; i < nd; i++) {
      PyObject* s = PyTuple_GetItem(dims_obj, i);
      Py_INCREF(s);
      PyTuple_SET_ITEM(shape, i, s);
    }
    PyObject* reshaped = call(arr, "reshape", Py_BuildValue("(O)", shape));
    Py_DECREF(shape);
    Py_DECREF(dims_obj);
    if (reshaped) { Py_DECREF(arr); arr = reshaped; }
  }
  PyObject* att = model_dict_attr(H(m.impl), "_c_api_attached");
  PyDict_SetItem(att, H(t.impl), arr);
  Py_DECREF(att);
  Py_DECREF(arr);
  return 0;
}

int flexflow_tensor_detach_raw_ptr(flexflow_model_t m, flexflow_tensor_t t) {
  PyObject* att = model_dict_attr(H(m.impl), "_c_api_attached");
  int ok = PyDict_DelItem(att, H(t.impl)) == 0 ? 0 : -1;
  if (ok != 0) PyErr_Clear();
  Py_DECREF(att);
  return ok;
}

static PyObject* tensor_host_data(PyObject* model, PyObject* tensor) {
  /* attached first, then the staged batch, then the staged label */
  PyObject* att = model_dict_attr(model, "_c_api_attached");
  PyObject* found = PyDict_GetItem(att, tensor);  /* borrowed */
  Py_XINCREF(found);
  Py_DECREF(att);
  if (found) return found;
  PyObject* staged = PyObject_GetAttrString(model, "_c_api_batch");
  if (staged && staged != Py_None) {
    found = PyDict_GetItem(staged, tensor);
    Py_XINCREF(found);
  }
  Py_XDECREF(staged);
  if (found) return found;
  PyErr_Clear();
  PyObject* label_t = PyObject_GetAttrString(model, "label_tensor");
  if (label_t == tensor) {
    found = PyObject_GetAttrString(model, "_c_api_label");
    if (found == Py_None) { Py_DECREF(found); found = nullptr; }
  }
  Py_XDECREF(label_t);
  PyErr_Clear();
  return found;
}

int flexflow_tensor_inline_map(flexflow_model_t m, flexflow_tensor_t t) {
  PyObject* data = tensor_host_data(H(m.impl), H(t.impl));
  if (!data) return -1;
  PyObject* contig = call(g_np, "ascontiguousarray",
                          Py_BuildValue("(O)", data));
  Py_DECREF(data);
  if (!contig) return -1;
  PyObject* mapped = model_dict_attr(H(m.impl), "_c_api_mapped");
  PyDict_SetItem(mapped, H(t.impl), contig);
  Py_DECREF(mapped);
  Py_DECREF(contig);
  return 0;
}

void flexflow_tensor_inline_unmap(flexflow_model_t m, flexflow_tensor_t t) {
  PyObject* mapped = model_dict_attr(H(m.impl), "_c_api_mapped");
  if (PyDict_DelItem(mapped, H(t.impl)) != 0) PyErr_Clear();
  Py_DECREF(mapped);
}

int flexflow_tensor_is_mapped(flexflow_model_t m, flexflow_tensor_t t) {
  PyObject* mapped = model_dict_attr(H(m.impl), "_c_api_mapped");
  int out = PyDict_GetItem(mapped, H(t.impl)) != nullptr;
  Py_DECREF(mapped);
  return out;
}

static void* mapped_ptr(flexflow_model_t m, flexflow_tensor_t t) {
  PyObject* mapped = model_dict_attr(H(m.impl), "_c_api_mapped");
  PyObject* arr = PyDict_GetItem(mapped, H(t.impl));  /* borrowed */
  Py_DECREF(mapped);
  if (!arr) return nullptr;
  PyObject* ct = PyObject_GetAttrString(arr, "ctypes");
  if (!ct) { PyErr_Print(); return nullptr; }
  PyObject* dp = PyObject_GetAttrString(ct, "data");
  Py_DECREF(ct);
  if (!dp) { PyErr_Print(); return nullptr; }
  void* p = (void*)PyLong_AsUnsignedLongLong(dp);
  Py_DECREF(dp);
  return p;
}

float* flexflow_tensor_get_raw_ptr_float(flexflow_model_t m,
                                         flexflow_tensor_t t) {
  return (float*)mapped_ptr(m, t);
}

int32_t* flexflow_tensor_get_raw_ptr_int32(flexflow_model_t m,
                                           flexflow_tensor_t t) {
  return (int32_t*)mapped_ptr(m, t);
}

int flexflow_tensor_get_num_dims(flexflow_tensor_t t) {
  PyObject* dims_obj = PyObject_GetAttrString(H(t.impl), "dims");
  if (!dims_obj) return -1;
  int nd = (int)PyTuple_Size(dims_obj);
  Py_DECREF(dims_obj);
  return nd;
}

int flexflow_tensor_get_data_type(flexflow_tensor_t t) {
  PyObject* dt = PyObject_GetAttrString(H(t.impl), "dtype");
  if (!dt) return -1;
  const char* s = PyUnicode_AsUTF8(dt);
  int out = 0;
  if (s && strstr(s, "int64")) out = 2;
  else if (s && strstr(s, "int")) out = 1;
  Py_DECREF(dt);
  return out;
}

flexflow_op_t flexflow_tensor_get_owner_op(flexflow_tensor_t t) {
  flexflow_op_t out{nullptr};
  out.impl = PyObject_GetAttrString(H(t.impl), "owner_op");
  if (out.impl == Py_None) { Py_DECREF(H(out.impl)); out.impl = nullptr; }
  if (!out.impl) PyErr_Clear();
  return out;
}

/* ---- dataloader handles --------------------------------------------- */

/* handle dict: model, tensor, input (np|None), label (np|None), num, next,
   is_label.  next_batch stages a [next, next+batch) slice the same way
   flexflow_model_set_input/set_label do, wrapping at num_samples —
   the reference's full-dataset-then-scatter pattern
   (python/flexflow_dataloader.cc:541-640). */

static void* loader_create(flexflow_model_t m, flexflow_tensor_t t,
                           const void* full_input, char in_fmt,
                           const int32_t* full_label, int64_t num_samples,
                           int is_label) {
  PyObject* model = H(m.impl);
  PyObject* d = PyDict_New();
  PyDict_SetItemString(d, "model", model);
  PyDict_SetItemString(d, "tensor", H(t.impl));
  PyObject* dims_obj = PyObject_GetAttrString(H(t.impl), "dims");
  int nd = dims_obj ? (int)PyTuple_Size(dims_obj) : 1;
  std::vector<int> dims(nd, 1);
  int64_t per_sample = 1;
  for (int i = 0; i < nd; i++) {
    dims[i] = (int)PyLong_AsLong(PyTuple_GetItem(dims_obj, i));
    if (i > 0) per_sample *= dims[i];
  }
  Py_XDECREF(dims_obj);
  dims[0] = (int)num_samples;
  if (full_input) {
    PyObject* arr = np_array(full_input, num_samples * per_sample,
                             dims.data(), nd, in_fmt);
    if (!arr) { Py_DECREF(d); return nullptr; }
    PyDict_SetItemString(d, "input", arr);
    Py_DECREF(arr);
  } else {
    /* fall back to a previously attached raw ptr (reference flow:
       attach_raw_ptr then SingleDataLoader) */
    PyObject* att = tensor_host_data(model, H(t.impl));
    if (att) {
      PyDict_SetItemString(d, "input", att);
      Py_DECREF(att);
    }
  }
  if (full_label) {
    int ldims[2] = {(int)num_samples, 1};
    PyObject* larr = np_array(full_label, num_samples, ldims, 2, 'i');
    if (!larr) { Py_DECREF(d); return nullptr; }
    PyDict_SetItemString(d, "label", larr);
    Py_DECREF(larr);
  }
  PyObject* n = PyLong_FromLongLong(num_samples);
  PyDict_SetItemString(d, "num", n);
  Py_DECREF(n);
  PyObject* z = PyLong_FromLong(0);
  PyDict_SetItemString(d, "next", z);
  Py_DECREF(z);
  PyObject* il = PyLong_FromLong(is_label);
  PyDict_SetItemString(d, "is_label", il);
  Py_DECREF(il);
  return d;
}

static int loader_next_batch(void* impl) {
  PyObject* d = H(impl);
  if (!d) return -1;
  PyObject* model = PyDict_GetItemString(d, "model");
  PyObject* tensor = PyDict_GetItemString(d, "tensor");
  PyObject* cfg = PyObject_GetAttrString(model, "config");
  PyObject* bs = cfg ? PyObject_GetAttrString(cfg, "batch_size") : nullptr;
  Py_XDECREF(cfg);
  if (!bs) { PyErr_Print(); return -1; }
  long batch = PyLong_AsLong(bs);
  Py_DECREF(bs);
  long num = PyLong_AsLong(PyDict_GetItemString(d, "num"));
  long next = PyLong_AsLong(PyDict_GetItemString(d, "next"));
  if (next + batch > num) next = 0;  /* wrap like DataLoader.reset */
  PyObject* lo = PyLong_FromLong(next);
  PyObject* hi = PyLong_FromLong(next + batch);
  PyObject* slice = PySlice_New(lo, hi, nullptr);
  Py_DECREF(lo);
  Py_DECREF(hi);
  int is_label = (int)PyLong_AsLong(PyDict_GetItemString(d, "is_label"));
  int ok = 0;
  for (const char* key : {"input", "label"}) {
    PyObject* arr = PyDict_GetItemString(d, key);
    if (!arr) continue;
    PyObject* part = PyObject_GetItem(arr, slice);
    if (!part) { PyErr_Print(); ok = -1; continue; }
    if (strcmp(key, "label") == 0 || is_label) {
      PyObject_SetAttrString(model, "_c_api_label", part);
      Py_DECREF(part);
    } else {
      flexflow_model_t mh{model};
      Py_INCREF(tensor);
      stage_input(mh, tensor, part);  /* steals part */
      Py_DECREF(tensor);
    }
  }
  Py_DECREF(slice);
  PyObject* nn = PyLong_FromLong(next + batch);
  PyDict_SetItemString(d, "next", nn);
  Py_DECREF(nn);
  return ok;
}

static void loader_reset(void* impl) {
  if (!impl) return;
  PyObject* z = PyLong_FromLong(0);
  PyDict_SetItemString(H(impl), "next", z);
  Py_DECREF(z);
}

static int64_t loader_num(void* impl) {
  return impl ? PyLong_AsLongLong(PyDict_GetItemString(H(impl), "num")) : -1;
}

static void loader_set_num(void* impl, int64_t n) {
  if (!impl) return;
  PyObject* v = PyLong_FromLongLong(n);
  PyDict_SetItemString(H(impl), "num", v);
  Py_DECREF(v);
}

flexflow_dataloader_4d_t flexflow_dataloader_4d_create(
    flexflow_model_t m, flexflow_tensor_t input, const float* full_input,
    const int32_t* full_label, int64_t num_samples) {
  flexflow_dataloader_4d_t out{
      loader_create(m, input, full_input, 'f', full_label, num_samples, 0)};
  return out;
}

flexflow_dataloader_4d_t flexflow_dataloader_4d_create_v2(
    flexflow_model_t m, flexflow_tensor_t input, const float* full_input,
    int64_t num_samples) {
  flexflow_dataloader_4d_t out{
      loader_create(m, input, full_input, 'f', nullptr, num_samples, 0)};
  return out;
}

void flexflow_dataloader_4d_destroy(flexflow_dataloader_4d_t d) {
  Py_XDECREF(H(d.impl));
}
void flexflow_dataloader_4d_reset(flexflow_dataloader_4d_t d) {
  loader_reset(d.impl);
}
int flexflow_dataloader_4d_next_batch(flexflow_dataloader_4d_t d,
                                      flexflow_model_t m) {
  (void)m;
  return loader_next_batch(d.impl);
}
int64_t flexflow_dataloader_4d_get_num_samples(flexflow_dataloader_4d_t d) {
  return loader_num(d.impl);
}
void flexflow_dataloader_4d_set_num_samples(flexflow_dataloader_4d_t d,
                                            int64_t n) {
  loader_set_num(d.impl, n);
}

flexflow_dataloader_2d_t flexflow_dataloader_2d_create(
    flexflow_model_t m, flexflow_tensor_t input, const float* full_input,
    const int32_t* full_label, int64_t num_samples) {
  flexflow_dataloader_2d_t out{
      loader_create(m, input, full_input, 'f', full_label, num_samples, 0)};
  return out;
}

flexflow_dataloader_2d_t flexflow_dataloader_2d_create_v2(
    flexflow_model_t m, flexflow_tensor_t input, const float* full_input,
    int64_t num_samples) {
  flexflow_dataloader_2d_t out{
      loader_create(m, input, full_input, 'f', nullptr, num_samples, 0)};
  return out;
}

void flexflow_dataloader_2d_destroy(flexflow_dataloader_2d_t d) {
  Py_XDECREF(H(d.impl));
}
void flexflow_dataloader_2d_reset(flexflow_dataloader_2d_t d) {
  loader_reset(d.impl);
}
int flexflow_dataloader_2d_next_batch(flexflow_dataloader_2d_t d,
                                      flexflow_model_t m) {
  (void)m;
  return loader_next_batch(d.impl);
}
int64_t flexflow_dataloader_2d_get_num_samples(flexflow_dataloader_2d_t d) {
  return loader_num(d.impl);
}
void flexflow_dataloader_2d_set_num_samples(flexflow_dataloader_2d_t d,
                                            int64_t n) {
  loader_set_num(d.impl, n);
}

flexflow_single_dataloader_t flexflow_single_dataloader_create(
    flexflow_model_t m, flexflow_tensor_t t, const void* full_data,
    int64_t num_samples, int is_float, int is_label) {
  flexflow_single_dataloader_t out{
      loader_create(m, t, full_data, is_float ? 'f' : 'i', nullptr,
                    num_samples, is_label)};
  return out;
}

void flexflow_single_dataloader_destroy(flexflow_single_dataloader_t d) {
  Py_XDECREF(H(d.impl));
}
void flexflow_single_dataloader_reset(flexflow_single_dataloader_t d) {
  loader_reset(d.impl);
}
int flexflow_single_dataloader_next_batch(flexflow_single_dataloader_t d,
                                          flexflow_model_t m) {
  (void)m;
  return loader_next_batch(d.impl);
}
int64_t flexflow_single_dataloader_get_num_samples(
    flexflow_single_dataloader_t d) {
  return loader_num(d.impl);
}
void flexflow_single_dataloader_set_num_samples(flexflow_single_dataloader_t d,
                                                int64_t n) {
  loader_set_num(d.impl, n);
}

}  // extern "C"
